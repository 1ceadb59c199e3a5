"""What the served decoder classes share (PR 35): the pieces of
``models/latent_moe_lm.LatentMoELM`` that ``models/sparse_linear_lm.
SparseLinearLM`` would otherwise copy, as plain functions of their arrays.

- ``rms`` / ``rotary`` / ``rotary_half`` / ``mm`` / ``ein`` / ``swiglu`` /
  ``ids``: the block's arithmetic (RMS norm, rotary over interleaved or
  half-split pairs, matmuls in the weights' type that accumulate in float32,
  the gated feed-forward, token ids out of a batch);
- ``topk_mask``: the exact k-th largest of every row as a mask, no sort;
- ``routed_experts``: the routed expert layer both MoE classes
  (``LatentMoELM``, ``models/window_moe_lm.WindowMoELM``) call: the route
  (sigmoid + selection bias, or softmax over the top-k), the pair sort, the
  ``moe_*`` counts, and ``expert_pairs``: the experts held over the sorted
  pairs (a decode step's through ``ops/expert_matmul``'s kernel, larger
  calls through ``ragged_dot`` slabs);
- ``attend_chunks``: softmax attention a chunk of keys at a time under a
  running maximum;
- ``bump`` / ``read_counters`` / ``no_counts`` / ``counts``: the device-side
  counters a class publishes through the paged contract's ``paged_counters``
  (an int32 ``(n, 2)`` leaf of the state, 61 bits a counter without int64);
- ``scope``: the ``zoo.lm.<stage>`` name a stage's operations carry in the
  compiled HLO.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.ops import expert_matmul
from analytics_zoo_tpu.ops.dispatch import resolve_impl

NEG_INF = -1e30
LIMB = 30               # a counter is (hi, lo) int32 with lo < 2 ** LIMB


def scope(name):
    return jax.named_scope("zoo.lm." + name)


def mm(x, W, out=jnp.float32):
    return jnp.matmul(x.astype(W.dtype), W, preferred_element_type=out)


def ein(spec, a, b, dtype):
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def rms(g, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def rotary(x, pos, theta):
    """Interleaved rotary on the LAST axis of ``x`` (T, ..., d): the pair
    (2i, 2i+1) turns by ``pos * theta ** (-2i / d)``."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def rotary_half(x, pos, theta):
    """Half-split rotary on the LAST axis of ``x`` (T, ..., d): the pair
    (i, i + d / 2) turns by ``pos * theta ** (-2i / d)`` (the ``rotate_half``
    form: two contiguous halves of the lanes, no stride-2 shuffle)."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def swiglu(h, gate, up, down):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def ids(x):
    x = jnp.asarray(x)
    if x.ndim == 3 and x.shape[-1] == 1:
        x = x[..., 0]
    return x.astype(jnp.int32)


def topk_mask(score, ok, k: int):
    """The ``k`` largest of each row of ``score`` (Q, S) among ``ok``,
    as a mask (all of ``ok`` where it has fewer than ``k``; among equal
    scores the earlier key first, as ``lax.top_k`` has it): the exact
    k-th largest found bit by bit over the floats' ordered integer
    images, 32 counting passes and no sort."""
    if k >= score.shape[-1]:
        return ok
    bits = jax.lax.bitcast_convert_type(score, jnp.int32)
    # monotone image of the float order in unsigned integers, >= 1
    u = jax.lax.bitcast_convert_type(
        bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF)), jnp.uint32) \
        ^ jnp.uint32(0x80000000)
    u = jnp.where(ok, jnp.maximum(u, jnp.uint32(1)), jnp.uint32(0))

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        enough = (u >= cand[:, None]).sum(-1) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros((score.shape[0],), jnp.uint32))[:, None]
    above = u > thr
    tie = ok & (u == thr)
    room = k - above.sum(-1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, axis=-1) <= room))


def attend_chunks(q, k, v, allowed, key_pos, last, scale, dtype,
                  key_chunk: int):
    """Softmax attention of queries ``q`` (Q, heads, d) over keys ``k`` /
    ``v`` (S, heads, d) under a mask, a chunk of ``key_chunk`` keys at a
    time with a running maximum, so that no (heads, Q, S) array exists at
    once (the TPU compiler serves a softmax over 8,192 keys at a twentieth
    of the speed of two over 4,096: PERF.md, PR 32).  ``allowed(lo, hi)``
    gives the mask of keys ``lo .. hi`` (anything that broadcasts to (heads,
    Q, hi - lo)) where it is used, so that a mask made of smaller parts
    never exists whole.  A chunk whose keys all lie after the queries' last
    position ``last`` is skipped.  Returns (Q, heads * d_v)."""
    n = k.shape[0]
    parts = []
    for lo in range(0, n, key_chunk):
        hi = min(lo + key_chunk, n)

        def chunk(lo=lo, hi=hi):
            att = ein("qhd,shd->hqs", q, k[lo:hi], dtype) * scale
            att = jnp.where(allowed(lo, hi), att, NEG_INF)
            m = att.max(-1)
            e = jnp.exp(att - m[..., None]).astype(dtype)
            return (m, e.sum(-1, dtype=jnp.float32),
                    ein("hqs,shd->hqd", e, v[lo:hi], dtype))

        def skip():
            hq = (q.shape[1], q.shape[0])
            return (jnp.full(hq, NEG_INF, jnp.float32),
                    jnp.zeros(hq, jnp.float32),
                    jnp.zeros(hq + (v.shape[-1],), jnp.float32))

        parts.append(chunk() if lo == 0 else jax.lax.cond(
            jnp.min(key_pos[lo:hi]) <= last, chunk, skip))
    top = functools.reduce(jnp.maximum, [m for m, _, _ in parts])
    den = sum(jnp.exp(m - top) * s for m, s, _ in parts)
    out = sum(jnp.exp(m - top)[..., None] * o for m, _, o in parts)
    return (out / den[..., None]).transpose(1, 0, 2).reshape(
        q.shape[0], -1)


def routed_experts(h, router_in, blk, valid, *, top_k: int, held, dtype,
                   scoring: str = "sigmoid", scale: float = 1.0,
                   act=jax.nn.silu, slab: int = 2048, impl=None):
    """A routed expert layer over tokens ``h`` (T, H), of which ``valid``
    (T,) are real; the router reads ``router_in`` (T, H) (``h`` itself, or
    the layer's input where the router stands before attention).  ``blk``
    holds ``router`` (H, experts) float32, ``w_gate`` / ``w_up`` (held, H,
    F) and ``w_down`` (held, F, H); ``e_bias`` (experts,) where the scoring
    has a selection bias.

    - ``scoring="sigmoid"``: ``s = sigmoid(router_in W_r)``, the ``top_k``
      largest of ``s + e_bias``; ``scoring="softmax"``: ``s =
      softmax(router_in W_r)``, its ``top_k`` largest.  Either way the
      chosen scores, normalised over the ``top_k`` and times ``scale``, are
      the pairs' weights (with ``softmax`` that is the softmax over the
      chosen logits);
    - ``held = (first, count)``: this process computes the pairs that land
      on experts ``first ... first + count - 1`` (a chip's share of an
      expert-parallel deployment); what the others would add is left out;
    - no pair is dropped at any imbalance: pairs sorted by held expert; an
      expert is ``act(x W_gate) * (x W_up)`` then ``W_down`` (``expert_pairs``,
      which ``slab`` and ``impl`` go to).

    Returns ``(y, counts)``: the routed output (T, H) float32 and
    ``{"pairs", "held", "busiest", "touched"}`` int32: pairs routed over all
    experts, those held here, those on the busiest held expert, held experts
    with at least one pair."""
    k, (first, count) = top_k, held
    with scope("moe_route"):
        logits = jnp.matmul(router_in, blk["router"],
                            precision=jax.lax.Precision.HIGHEST)
        if scoring == "sigmoid":
            s = jax.nn.sigmoid(logits)
            _, idx = jax.lax.top_k(s + blk["e_bias"], k)          # (T, k)
        elif scoring == "softmax":
            s = jax.nn.softmax(logits, axis=-1)
            _, idx = jax.lax.top_k(s, k)
        else:
            raise ValueError(f"scoring must be sigmoid|softmax, got "
                             f"{scoring!r}")
        g = jnp.take_along_axis(s, idx, axis=-1)
        g = scale * g / (g.sum(-1, keepdims=True) + 1e-20)
        local = idx - first
        on = (local >= 0) & (local < count) & valid[:, None]
        # pairs sorted by held expert; what is not held sorts behind
        key = jnp.where(on, local, count).reshape(-1)
        order = jnp.argsort(key)
        tok, gate = order // k, g.reshape(-1)[order]
        sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    y = expert_pairs(h, tok, gate, sizes, blk, dtype=dtype, act=act,
                     slab=slab, impl=impl)
    return y, {"pairs": valid.sum().astype(jnp.int32) * k,
               "held": sizes.sum(), "busiest": sizes.max(),
               "touched": (sizes > 0).sum().astype(jnp.int32)}


def expert_pairs(h, tok, gate, sizes, blk, *, dtype, act=jax.nn.silu,
                 slab: int = 2048, impl=None):
    """``routed_experts``' experts over its sorted pairs: pair p is token
    ``tok[p]`` of ``h`` (T, H) with weight ``gate[p]``; held expert e owns
    the next ``sizes[e]`` pairs, from pair 0 on; pairs past ``sum(sizes)``
    are held by none.  A call of at most ``expert_matmul.MAX_PAIRS`` pairs
    (a decode step) runs through ``expert_matmul.grouped_expert_ffn``, which
    reads each touched expert's weights once, back to back, unless ``impl``
    (``ops/dispatch.resolve_impl``) is ``xla`` (a CPU's); a larger call
    (prefill), or ``xla``, runs slabs of ``slab`` pairs through
    ``jax.lax.ragged_dot`` until none is left (the kernel's oracle).
    Returns the weighted sum over each token's pairs, (T, H) float32."""
    T, H = h.shape
    P = tok.shape[0]
    with scope("moe_experts"):
        mode = resolve_impl(impl)
        if mode != "xla" and P <= expert_matmul.MAX_PAIRS:
            out = expert_matmul.grouped_expert_ffn(
                jnp.take(h, tok, axis=0).astype(dtype), blk["w_gate"],
                blk["w_up"], blk["w_down"], sizes, act=act,
                interpret=mode == "interpret")
            # the rows of pairs held by none come out zero
            return jnp.zeros((T, H), jnp.float32).at[tok].add(
                out * gate[:, None])
        ends = jnp.cumsum(sizes)
        total = ends[-1]
        R = min(slab, P)
        # the pair list is padded so that its last slab is whole
        pad = (-P) % R
        tok = jnp.concatenate([tok, jnp.zeros((pad,), tok.dtype)])
        gate = jnp.concatenate([gate, jnp.zeros((pad,), gate.dtype)])

        def one_slab(i, y):
            # pairs [lo, lo + R) of the sorted list: each expert's rows
            # inside the slab are one group of the grouped matmul
            lo = i * R
            rows = jax.lax.dynamic_slice(tok, (lo,), (R,))
            wts = jax.lax.dynamic_slice(gate, (lo,), (R,))
            gs = jnp.clip(ends, lo, lo + R) \
                - jnp.clip(ends - sizes, lo, lo + R)
            x = jnp.take(h, rows, axis=0).astype(dtype)

            def gmm(a, W):
                return jax.lax.ragged_dot(
                    a, W, gs, preferred_element_type=jnp.float32)

            mid = act(gmm(x, blk["w_gate"])) * gmm(x, blk["w_up"])
            out = gmm(mid.astype(dtype), blk["w_down"])
            live = (lo + jnp.arange(R) < total)[:, None]
            return y.at[rows].add(jnp.where(live, out * wts[:, None], 0.0))

        return jax.lax.fori_loop(0, (total + R - 1) // R, one_slab,
                                 jnp.zeros((T, H), jnp.float32))


def no_counts(names):
    return jnp.zeros((len(names),), jnp.int32)


def counts(names, **named):
    """A call's increments of the counters ``names``: ``named`` values at
    their places, zeros elsewhere."""
    c = no_counts(names)
    for name, value in named.items():
        c = c.at[names.index(name)].set(jnp.asarray(value, jnp.int32))
    return c


def bump(counters, counts):
    """Add a call's ``counts`` (each < 2 ** 30) to the (n, 2) int32
    ``(hi, lo)`` counters, which so hold 61 bits without int64."""
    lo = counters[:, 1] + counts
    return jnp.stack([counters[:, 0] + (lo >> LIMB),
                      lo & ((1 << LIMB) - 1)], axis=1)


def read_counters(counters, names) -> dict:
    """The counters leaf as Python ints by name, read on the host (one
    small transfer; the caller owns the state, which must not be in a
    call's hands)."""
    c = np.asarray(counters).astype(np.int64)
    return {name: int((c[i, 0] << LIMB) + c[i, 1])
            for i, name in enumerate(names)}
