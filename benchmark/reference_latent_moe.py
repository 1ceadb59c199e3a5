"""Plain reference of a latent-attention decoder with a learned sparse selection
and routed experts, and the comparison that decides ``correct`` for it.

Nothing here calls the model code under test.  The forward is written out over the
parameter tree the program's ``build`` returns (upcast to float32 a layer, an expert
at a time, so that it fits beside the served system), in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no cache, no kernel, no batching, one
sequence, a layer and a block of queries at a time.  ``cfg`` is the configuration
file: the published ``config.json`` keys as the file cuts them, ``published`` (the
source's value of each key under ``reduced``) and ``deployment``.

Equations (one layer; ``h`` the residual stream, RMS norm eps ``rms_norm_eps``, no
biases; T tokens at positions 0..T-1):

- MLA.  ``c_q = RMSNorm(RMSNorm(h) W_qa)``; ``q = c_q W_qb`` -> heads x (nope | rope);
  ``[c_kv | k_r] = RMSNorm(h) W_kva``; ``c_kv = RMSNorm(c_kv)``; rotary (theta,
  interleaved pairs (2i, 2i+1)) on q's rope part and on ``k_r``, which all heads
  share; ``[k_nope | v] = c_kv W_kvb``; ``score = (q_nope . k_nope + q_rope . k_r) /
  sqrt(nope + rope)``; softmax over the ALLOWED keys; ``o = (sum p v) W_o``.
- Indexer.  ``q_I = c_q W_Iq`` -> index heads x index dim; ``k_I = LayerNorm(RMSNorm(h)
  W_Ik)`` (one head, eps 1e-6); rotary on the first ``rope`` dims of both; ``w =
  RMSNorm(h) W_Iw``; ``I[t, s] = sum_j w[t, j] ReLU(q_I[t, j] . k_I[s]) * heads^-0.5 *
  dim^-0.5``; the allowed keys of query t are the ``min(index_topk, t + 1)`` causal s
  of largest ``I[t, s]``.
- Routed FFN.  ``s = sigmoid(RMSNorm(h) W_g)`` over ALL experts; the
  ``num_experts_per_tok`` largest of ``s + b``; ``g = scale * s[chosen] / (sum
  s[chosen] + 1e-20)``; ``y = sum_{e chosen AND held} g_e SwiGLU_e + SwiGLU_shared``.
- Stack.  Pre-norm residual blocks, the first ``first_k_dense_replace`` with a dense
  SwiGLU; final RMS norm; untied head.

Departures from the published model, each shared with the program: no
multi-token-prediction layer (the base model's logits do not depend on it); the
indexer runs in the model's own precision, without its FP8 cast and Hadamard rotation
(q . k is unchanged by the rotation in exact arithmetic); the HELD share of the
experts only (``n_routed_experts`` of ``published.n_routed_experts``, the share
``deployment.chip``): what the absent experts would add is left out; the vocabulary
is the slice ``vocab_size`` rows wide.  Published rotary code de-interleaves the pairs
before turning them, which permutes q's and k's rope dims alike and leaves every
score as it is here.
"""

from __future__ import annotations

import functools
import json

import numpy as np

QUERY_BLOCK = 256

# What decides ``correct`` for served tokens (``check_served``): in the reference's
# teacher-forced forward of the same weights, how far each served token's logit lies
# under that position's best (0 where the served token IS the reference's best).  The
# served path multiplies in bfloat16, so its hidden states differ from the
# reference's by a fraction of a percent: a near-tie of logits may resolve otherwise
# (the logits spread over 1.58 a unit, the best two lie ~0.3 apart), and so may a
# near-tie of the ROUTER's 8th and 9th expert or of the selection's 2048th and 2049th
# key, which moves that position's logits by ~0.1-0.5 (an expert's output arrives or
# leaves whole).  Hence two limits, each set between two readings taken at the
# published widths on one v5e chip (PERF.md, section 4; my chip runs, PR 32):
#   mean margin: the served system reads 0.014-0.043 (a quarter of the tokens off the
#     reference's best, by little); the reference's OWN tokens when it multiplies in
#     float8_e4m3fn, the next precision under the configuration's bfloat16, read 3.11
#     (86 of 89 tokens off): the limit 0.3 is 7 x over the first, 10 x under the second.
#   max margin: served 0.36-1.11, float8 6.77: the limit 3.0.  A wrong cache block,
#     position or expert shows as margins of several units at every later token.
# (The reference in bfloat16 reads 0.013 / 0.36 where the served system read 0.014 /
# 0.36: what the system shows is bfloat16's own.)
MEAN_MARGIN_TOL = 0.3
MAX_MARGIN_TOL = 3.0


def _f32(a):
    import jax.numpy as jnp
    return jnp.asarray(a, jnp.float32)


def _rms(g, x, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


def _rotary(x, pos, theta):
    """Pairs (2i, 2i+1) of the last axis of ``x`` (T, ..., d) turned by
    ``pos * theta ** (-2i / d)``."""
    import jax.numpy as jnp
    d = x.shape[-1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = _f32(np.asarray(pos, np.float64)[:, None] * freq[None, :])
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def held_experts(cfg: dict):
    """``(first, count, router width)`` of the share the file describes."""
    held = int(cfg["n_routed_experts"])
    total = int((cfg.get("published") or {}).get("n_routed_experts", held))
    chip = int((cfg.get("deployment") or {}).get("chip", 0))
    return chip * held, held, total


def _lower(round_to):
    """``r(x)``: ``x`` through the precision ``round_to`` and back (identity for
    None): how a lower-precision matmul sees its operands."""
    if round_to is None:
        return lambda x: x
    return lambda x: _f32(_f32(x).astype(round_to))


def attention(blk, cfg, h, r):
    """MLA under the indexer's selection over one sequence ``h`` (T, H) at positions
    0..T-1, a block of queries at a time.  Returns ``(output (T, H), keys allowed a
    query (T,))``."""
    import jax
    import jax.numpy as jnp
    T = h.shape[0]
    pos = np.arange(T)
    nh, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rope, vd, rank = cfg["qk_rope_head_dim"], cfg["v_head_dim"], \
        cfg["kv_lora_rank"]
    ih, idim, topk = cfg["index_n_heads"], cfg["index_head_dim"], \
        cfg["index_topk"]
    eps = cfg["rms_norm_eps"]
    theta = (cfg.get("rope_parameters") or {}).get("rope_theta", 1e6)

    def mm(x, w):
        return r(x) @ r(_f32(w))

    c_q = _rms(blk["q_a_ln"], mm(h, blk["q_a"]), eps)
    q = mm(c_q, blk["q_b"]).reshape(T, nh, nope + rope)
    q_nope, q_rope = q[..., :nope], _rotary(q[..., nope:], pos, theta)
    kv = mm(h, blk["kv_a"])
    c_kv = _rms(blk["kv_a_ln"], kv[:, :rank], eps)
    k_r = _rotary(kv[:, rank:], pos, theta)
    kvb = mm(c_kv, blk["kv_b"]).reshape(T, nh, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]

    def rot_first(a):
        return jnp.concatenate([_rotary(a[..., :rope], pos, theta),
                                a[..., rope:]], axis=-1)

    q_i = rot_first(mm(c_q, blk["wq_b"]).reshape(T, ih, idim))
    k_i = mm(h, blk["wk"])
    mu = jnp.mean(k_i, axis=-1, keepdims=True)
    var = jnp.mean((k_i - mu) ** 2, axis=-1, keepdims=True)
    k_i = rot_first((k_i - mu) / jnp.sqrt(var + 1e-6) * _f32(blk["k_ln"]["g"])
                    + _f32(blk["k_ln"]["b"]))
    w_i = mm(h, blk["w_proj"]) * (ih ** -0.5 * idim ** -0.5)
    s_idx = jnp.arange(T)

    def block(args):
        t, qn, qr, qi, wi = args
        causal = s_idx[None, :] <= t[:, None]
        index = jnp.einsum("qjs,qj->qs", jax.nn.relu(jnp.einsum(
            "qjd,sd->qjs", r(qi), r(k_i))), wi)
        index = jnp.where(causal, index, -jnp.inf)
        if topk < T:        # each query's best keys, the earlier of equals first
            best = jnp.argsort(-index, axis=-1, stable=True)[:, :topk]
            allowed = causal & jnp.zeros_like(causal).at[
                jnp.arange(len(t))[:, None], best].set(True)
        else:
            allowed = causal
        score = (jnp.einsum("qhd,shd->hqs", r(qn), r(k_nope))
                 + jnp.einsum("qhd,sd->hqs", r(qr), r(k_r))) \
            / np.sqrt(nope + rope)
        p = jax.nn.softmax(jnp.where(allowed[None], score, -jnp.inf), axis=-1)
        return (jnp.einsum("hqs,shd->qhd", r(p), r(v)).reshape(-1, nh * vd),
                allowed.sum(-1))

    qb = min(QUERY_BLOCK, T)

    def blocked(a):
        return a.reshape((T // qb, qb) + a.shape[1:])

    o, n_allowed = jax.lax.map(block, tuple(map(
        blocked, (s_idx, q_nope, q_rope, q_i, w_i))))
    return mm(o.reshape(T, nh * vd), blk["o"]), n_allowed.reshape(T)


def swiglu(h, gate, up, down, r):
    import jax
    a = r(h) @ r(_f32(gate))
    return r(jax.nn.silu(a) * (r(h) @ r(_f32(up)))) @ r(_f32(down))


def route(blk, cfg, h):
    """``(chosen (T, k) expert ids over ALL experts, gates (T, k))``."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(h @ _f32(blk["router"]))
    chosen = jnp.argsort(-(s + _f32(blk["e_bias"])), axis=-1, stable=True)[
        :, :cfg["num_experts_per_tok"]]
    g = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, cfg.get("routed_scaling_factor", 2.5) * g / (
        g.sum(-1, keepdims=True) + 1e-20)


def routed_ffn(blk, cfg, h, r, share=None, shared_expert=True):
    """The expert layer's output over ``h`` (T, H) from the experts ``share`` =
    ``(first, count)`` of the file's share (default: all of it): each expert in turn
    over EVERY token, times the token's gate for it (zero where the token did not
    choose it).  Returns ``(y, chosen)``."""
    import jax
    import jax.numpy as jnp
    first, count, _ = held_experts(cfg)
    lo, n = share or (0, count)
    chosen, gates = route(blk, cfg, h)

    def expert(y, e):
        gate = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), axis=-1)
        return y + gate[:, None] * swiglu(
            h, blk["w_gate"][e], blk["w_up"][e], blk["w_down"][e], r), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), lo + jnp.arange(n))
    if shared_expert:
        y = y + swiglu(h, blk["s_gate"], blk["s_up"], blk["s_down"], r)
    return y, chosen


def layer(blk, cfg, x, round_to=None):
    """One block over ``x`` (T, H).  Returns ``(x, what the layer decided)``: the
    keys allowed a query (T,) and the experts chosen a token (T, k), None in a dense
    layer."""
    r = _lower(round_to)
    eps = cfg["rms_norm_eps"]
    o, n_allowed = attention(blk, cfg, _rms(blk["ln1"], x, eps), r)
    x = x + o
    h = _rms(blk["ln2"], x, eps)
    if "router" in blk:
        y, chosen = routed_ffn(blk, cfg, h, r)
        return x + y, {"allowed": n_allowed, "chosen": chosen}
    return x + swiglu(h, blk["gate"], blk["up"], blk["down"], r), \
        {"allowed": n_allowed, "chosen": None}


@functools.lru_cache(maxsize=8)
def _jitted_layer(cfg_json: str, round_to):
    import jax
    cfg = json.loads(cfg_json)
    return jax.jit(lambda blk, x: layer(blk, cfg, x, round_to))


def logits(params, cfg: dict, ids, rows=None, round_to=None,
           probe=None) -> np.ndarray:
    """Teacher-forced float32 logits of the sequence ``ids`` (T,) at positions
    ``rows`` (default: all), (len(rows), vocab).  ``round_to`` computes every
    matmul over operands rounded to that type: the reading a lower precision gives.
    ``probe`` (a list) receives what each layer decided (``layer``).  A layer is one
    jitted function (of the sequence's length), called once per layer."""
    import jax
    import jax.numpy as jnp
    ids = np.asarray(ids, np.int32)
    if len(ids) > QUERY_BLOCK and len(ids) % QUERY_BLOCK:
        raise ValueError(f"pad the sequence to a multiple of {QUERY_BLOCK}")
    rows = np.arange(len(ids)) if rows is None else np.asarray(rows)
    r = _lower(round_to)
    step = _jitted_layer(json.dumps(cfg, sort_keys=True), round_to)
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], jnp.asarray(ids), axis=0))
        for blk in params["blocks"]:
            x, seen = step(blk, x)
            if probe is not None:
                probe.append(jax.tree.map(np.asarray, seen))
        h = _rms(params["ln_f"], x[rows], cfg["rms_norm_eps"])
        return np.asarray(r(h) @ r(_f32(params["head"])))


def margins(params, cfg: dict, ids, prompt_len: int, pad_to: int,
            round_to=None):
    """``ids`` = prompt + served tokens, right-padded to ``pad_to`` (causal attention
    makes the padding harmless, and one padded length is one set of compiled
    shapes).  Returns, for each served token, (best logit at its position) - (its own
    logit)."""
    n = len(ids) - prompt_len
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(ids)] = ids
    out = logits(params, cfg, padded,
                 np.arange(prompt_len - 1, prompt_len - 1 + n), round_to)
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
