"""A decoder-hybrid-decoder: a SELF-decoder of selective state-space (Mamba)
layers and sliding-window differential-attention layers, one full
differential-attention layer whose K/V is the model's only full-context
cache, and a CROSS-decoder whose attention layers read that one cache and
whose gated memory units (GMU) gate the last Mamba layer's output.

``SSMHybridLM`` is the block of the SambaY family (``mb_per_layer`` places
the Mamba layers, ``sliding_window`` bounds the window layers), served
through the same paged contract as ``models/textmodels.TransformerLM`` (its
docstring is the contract's text).  With N layers and h = N / 2, layer l is

- M (Mamba) where ``l % mb_per_layer == 0`` and ``l <= h``; the last of them
  (l = h) also emits the MEMORY ``m_t``: its gated output before ``W_out``;
- W (window) for the other layers below h: differential attention over keys
  ``i - window < j <= i``;
- F (full) at l = h + 1: differential attention over ``j <= i``; its K/V are
  cached whole;
- G (GMU) where ``l % mb_per_layer == 0`` past h + 1: ``(m_t * silu(h W_g))
  W_o``, no state of its own;
- C (cross) for the other layers past h + 1: queries only, differential
  attention over layer F's K/V, ``j <= i``, no cache of its own.

Every layer computes ``x += mixer(LN1(x))``, then ``[g | u] = LN2(x) W1``,
``x += (silu(g) * u) W2`` on the float32 residual ``x`` (LayerNorms with a
gain and a bias, no projection bias, no positional encoding); ``logits =
LN_f(x) E^T`` over the tied embedding.

A Mamba layer: ``[u | z] = h W_in``; ``u = silu(conv(u) + b)`` (causal,
depthwise, width ``mamba_d_conv``); ``[dt | B | C] = u W_x``; ``delta =
softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; the selective scan of
``ops/selective_scan``; ``y = (C s + D u) * silu(z)``; ``out = y W_out``.

Differential attention (head width d = hidden / heads): heads in pairs, query
pair i (heads 2i, 2i + 1) of key pair g = i // (heads / kv heads) (key heads
2g, 2g + 1, value ``[v_2g | v_2g+1]``); ``a_j = softmax(q_j k_j^T / sqrt(d))
V``; ``o_i = (1 - lambda_init) RMSNorm(a_1 - lambda a_2)`` with ``lambda =
exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6
exp(-0.3 l)``.  A key pair ``[k_2g | k_2g+1]`` and its value are one 2d-wide
key head of ``ops/paged_attention``'s grouped pages, and each query pair is
two query rows of it, ``[q1 | 0]`` and ``[0 | q2]``, scaled by sqrt(2) so
that the pages' 1 / sqrt(2d) is the published 1 / sqrt(d): the grouped-page
kernel reads the cache as it reads ``WindowMoELM``'s, exactly.

The state the scheduler carries (one opaque pytree) is ``ops/paged_attention``
's window format: layer F's K/V in grouped pages read through the block table
(``k`` / ``v``, one layer), each window layer's K/V in a per-slot ring
(``rk`` / ``rv``); beside it each Mamba layer's per-slot ``conv`` (slots,
d_conv - 1, channels) and ``ssm`` (slots, d_state, channels) state in
``state_dtype`` (float32), and ``counters``.  A slot's rings and Mamba state
are its own, so ``paged_prefix_sharing`` is False.

Prefill runs a batch's rows in a ``lax.scan`` (a padding row is skipped
whole).  The self-decoder runs in ONE ``lax.scan`` over its (Mamba,
attention) pairs, the weights stacked by kind, ``_POS_CHUNK`` positions at a
time (a chunk of padding alone is not computed); the Mamba scan carries its
``(conv, ssm)`` state from chunk to chunk, and an attention layer's query
blocks of ``_QUERY_BLOCK`` read only their window's key chunks.  The
cross-decoder needs no earlier position of its own (its attention reads layer
F's K/V, its gates the memory), so a prefill runs it over each row's LAST
real position only, in a second ``lax.scan`` over (GMU, cross) pairs;
``call`` runs it over every position.  Decode runs one token a row through
every layer, unrolled: the scan's one-step form, ``ring_put`` into the
window rings, layer F's append to the pages, and layer F and the cross
layers reading the pages through ``grouped_paged_attention`` on a TPU (the
XLA gather of every row's whole table on a CPU, and the kernel's oracle).

Not built: sharing a resident prefix (``prefill_shared_paged`` raises: the
Mamba state and the rings at the prefix's end are no resident block's),
contiguous caches (``init_decode`` / ``decode_step`` raise).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.models import lm_common as common
from analytics_zoo_tpu.models.lm_common import NEG_INF, scope as _scope
from analytics_zoo_tpu.models.window_moe_lm import _attend_loop
from analytics_zoo_tpu.nn.module import Layer
from analytics_zoo_tpu.ops import paged_attention as paged
from analytics_zoo_tpu.ops import selective_scan as sscan
from analytics_zoo_tpu.ops.dispatch import resolve_impl

_POS_CHUNK = 1024       # prefill positions a layer takes at once
_QUERY_BLOCK = 256      # ... of which the attention takes this many queries
_KEY_CHUNK = 1024       # a full or cross layer's keys a block takes at once
_WINDOW_CHUNK = 256     # a window layer's keys a block takes at once
_SCAN_UNROLL = 8        # positions one trip of the prefill scan takes

# What the programs count, in the order of the state's ``counters`` leaf.
# ``prefill_*`` and ``ssm_*`` over prefill rows (a padding row counts
# nothing); ``window_keys_*`` over decode rows (idle slots left out) x window
# layers; ``full_keys_*`` over decode rows x the layers that read the pages
# (layer F and the cross layers).
COUNTERS = (
    "prefill_positions_real",     # real prompt positions prefilled
    "prefill_cross_positions",    # layer-positions the cross-decoder ran
    "ssm_positions_scanned",      # Mamba layer-positions scanned (chunks)
    "ssm_positions_real",         # ... of which real
    "window_keys_attended",       # keys a window layer read: min(ctx, window)
    "window_keys_context",        # keys in context there
    "full_keys_read",             # positions a page read fetched
    "full_keys_context",          # keys in context there: pos + 1
)

MAMBA, WINDOW, FULL, GMU, CROSS = "M", "W", "F", "G", "C"
_GROUP = {MAMBA: "mamba", WINDOW: "attn", FULL: "attn", GMU: "gmu",
          CROSS: "cross"}


def _count(**named):
    return common.counts(COUNTERS, **named)


def layer_kinds(n_layers: int, mb_per_layer: int) -> tuple:
    """Each layer's kind, from the two keys that place them."""
    h = n_layers // 2
    return tuple((MAMBA if l % mb_per_layer == 0 else WINDOW) if l <= h
                 else FULL if l == h + 1
                 else (GMU if l % mb_per_layer == 0 else CROSS)
                 for l in range(n_layers))


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


class SSMHybridLM(Layer):
    """See the module docstring.  Constructor arguments carry the names of
    the published ``config.json`` (the ``mamba_*`` sizes are the family's
    defaults, which it does not print); ``from_config`` reads one."""

    # the scheduler refuses ``prefix_cache`` over this class at start
    paged_prefix_sharing = False
    # the type of the Mamba layers' per-slot ``conv`` and ``ssm`` state
    state_dtype = jnp.float32

    def __init__(self, vocab_size: int, hidden_size: int,
                 intermediate_size: int, num_hidden_layers: int,
                 num_attention_heads: int, num_key_value_heads: int,
                 sliding_window: int, mb_per_layer: int,
                 mamba_d_state: int = 16, mamba_d_conv: int = 4,
                 mamba_expand: int = 2, mamba_dt_rank: Optional[int] = None,
                 layer_norm_eps: float = 1e-5,
                 tie_word_embeddings: bool = True, mlp_bias: bool = False,
                 lm_head_bias: bool = False, hidden_act: str = "silu",
                 dtype: str = "bfloat16", initializer_range: float = 0.02,
                 **kwargs):
        super().__init__(**kwargs)
        if not tie_word_embeddings or mlp_bias or lm_head_bias \
                or hidden_act != "silu":
            raise ValueError("this class serves a tied head, no biases and "
                             "a SiLU-gated MLP")
        self.vocab_size = int(vocab_size)
        self.hidden = int(hidden_size)
        self.ffn = int(intermediate_size)
        self.n_layers = int(num_hidden_layers)
        self.n_head, self.n_kv = int(num_attention_heads), \
            int(num_key_value_heads)
        if self.hidden % self.n_head or self.n_head % self.n_kv \
                or self.n_kv % 2:
            raise ValueError(f"{self.n_head} heads over {self.n_kv} key heads "
                             f"of width {self.hidden}: differential attention "
                             f"needs pairs of key heads")
        self.head_dim = self.hidden // self.n_head
        self.n_groups = self.n_kv // 2            # key pairs: pages' key heads
        self.rows = 2 * (self.n_head // self.n_kv)  # query rows a key pair
        self.window = int(sliding_window)
        self.d_state, self.d_conv = int(mamba_d_state), int(mamba_d_conv)
        self.d_inner = int(mamba_expand) * self.hidden
        self.dt_rank = int(mamba_dt_rank) if mamba_dt_rank \
            else -(-self.hidden // 16)
        self.eps = float(layer_norm_eps)
        self.dtype = jnp.dtype(dtype)
        self.std = float(initializer_range)
        kinds = layer_kinds(self.n_layers, int(mb_per_layer))
        h = self.n_layers // 2
        want = (MAMBA, WINDOW) * (h // 2) + (MAMBA, FULL) \
            + (GMU, CROSS) * ((self.n_layers - h - 2) // 2)
        if self.n_layers % 4 or kinds != want:
            raise ValueError(
                f"layer kinds {''.join(kinds)}: this class serves (Mamba, "
                f"window) pairs up to a Mamba layer at N/2, the full layer "
                f"after it, then (GMU, cross) pairs (mb_per_layer 2, N a "
                f"multiple of 4)")
        self.kinds = kinds
        # each layer's place in its kind's weight stack
        self.index = [sum(_GROUP[x] == _GROUP[k] for x in kinds[:l])
                      for l, k in enumerate(kinds)]
        self.n_pairs = kinds.count(MAMBA)         # self-decoder pairs
        self.n_window = kinds.count(WINDOW)
        self.n_cross = kinds.count(CROSS)
        self.attn_ids = [l for l, k in enumerate(kinds) if k in (WINDOW, FULL)]
        self.cross_ids = [l for l, k in enumerate(kinds) if k == CROSS]
        self._declared_input_shape = (None,)

    @classmethod
    def from_config(cls, cfg: dict, **overrides) -> "SSMHybridLM":
        """From a published ``config.json`` as a configuration file gives
        it.  Keys the class does not know are not read."""
        import inspect
        known = set(inspect.signature(cls.__init__).parameters) - {"self"}
        kw = {k: v for k, v in cfg.items() if k in known}
        kw.update(overrides)
        return cls(**kw)

    # -- weights --------------------------------------------------------------
    def build(self, rng, input_shape=None):
        """Random weights from ``rng``: matmul weights normal
        (``initializer_range``) in ``dtype``; LayerNorm gains 1 + 0.1 n and
        biases 0.1 n, the differential heads' norm gains 1 + 0.1 n and the
        lambda vectors 0.1 n, in float32; the Mamba layers as the family
        initialises them: ``A_log = log(1 .. d_state)`` a channel, ``D = 1``,
        ``b_dt`` the inverse softplus of a step drawn log-uniform in [0.001,
        0.1], ``W_dt`` uniform within ``dt_rank ** -0.5``, the convolution
        uniform within ``d_conv ** -0.5`` (float32).  Weights are stacked by
        kind on a leading axis: ``mamba``, ``attn`` (window layers, then the
        full layer), ``gmu``, ``cross``."""
        H, F, Di, N, K, R = self.hidden, self.ffn, self.d_inner, \
            self.d_state, self.d_conv, self.dt_rank
        dt, std, f32 = self.dtype, self.std, jnp.float32
        nkv = self.n_kv * self.head_dim
        keys = iter(jax.random.split(rng, 64))

        def w(*shape, dtype=dt, scale=std):
            return (scale * jax.random.normal(next(keys), shape, f32)
                    ).astype(dtype)

        def uniform(shape, bound):
            return jax.random.uniform(next(keys), shape, f32, -bound, bound)

        def small(*shape):
            return w(*shape, dtype=f32, scale=0.1)

        def layers(n, **mixer):
            """``n`` layers' norms and MLP beside their mixer's weights."""
            return dict(mixer, ln1_g=1.0 + small(n, H), ln1_b=small(n, H),
                        ln2_g=1.0 + small(n, H), ln2_b=small(n, H),
                        w1=w(n, H, 2 * F), w2=w(n, F, H))

        def differential(n):
            d = self.head_dim
            return dict(lq1=small(n, d), lk1=small(n, d), lq2=small(n, d),
                        lk2=small(n, d), subln=1.0 + small(n, 2 * d))

        nm, ng = self.n_pairs, self.n_cross
        step = jnp.exp(jax.random.uniform(next(keys), (nm, Di), f32,
                                          math.log(1e-3), math.log(1e-1)))
        return {
            "embed": w(self.vocab_size, H),
            "ln_f_g": 1.0 + small(H), "ln_f_b": small(H),
            "mamba": layers(
                nm, w_in=w(nm, H, 2 * Di),
                conv_w=uniform((nm, K, Di), K ** -0.5),
                conv_b=uniform((nm, Di), K ** -0.5),
                w_x=w(nm, Di, R + 2 * N),
                w_dt=uniform((nm, R, Di), R ** -0.5).astype(dt),
                b_dt=step + jnp.log(-jnp.expm1(-step)),   # softplus^-1(step)
                A_log=jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=f32)),
                                       (nm, Di, N)),
                D=jnp.ones((nm, Di), f32), w_out=w(nm, Di, H)),
            "attn": layers(nm, **differential(nm), w_qkv=w(nm, H, H + 2 * nkv),
                           w_o=w(nm, H, H)),
            "gmu": layers(ng, w_g=w(ng, H, Di), w_o=w(ng, Di, H)),
            "cross": layers(ng, **differential(ng), w_q=w(ng, H, H),
                            w_o=w(ng, H, H)),
        }

    def matmul_operands(self, params, dtype):
        """The tree is built in its operand type: nothing to round."""
        return params

    # -- shared pieces --------------------------------------------------------
    def _ln(self, blk, name, x):
        mu = x.mean(-1, keepdims=True)
        xc = x - mu
        return xc * jax.lax.rsqrt((xc * xc).mean(-1, keepdims=True)
                                  + self.eps) * blk[name + "_g"] \
            + blk[name + "_b"]

    def _embed(self, params, ids):
        return jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)

    def _logits(self, params, x):
        h = self._ln(params, "ln_f", x)
        return jnp.einsum("th,vh->tv", h.astype(self.dtype), params["embed"],
                          preferred_element_type=jnp.float32)

    def _mlp(self, blk, x):
        gu = common.mm(self._ln(blk, "ln2", x), blk["w1"])
        return common.mm(jax.nn.silu(gu[:, :self.ffn]) * gu[:, self.ffn:],
                         blk["w2"])

    def _queries(self, q):
        """Queries (T, heads * d) as the pages' query rows (T, key pairs,
        rows, 2d): pair i's ``[q1 | 0]`` and ``[0 | q2]``, times sqrt(2)."""
        T, d = q.shape[0], self.head_dim
        q = q.reshape(T, self.n_groups, self.rows // 2, 2, d) * math.sqrt(2.0)
        z = jnp.zeros(q.shape[:-2] + (d,), q.dtype)
        q4 = jnp.stack([jnp.concatenate([q[..., 0, :], z], -1),
                        jnp.concatenate([z, q[..., 1, :]], -1)], axis=-2)
        return q4.reshape(T, self.n_groups, self.rows, 2 * d)

    def _qkv(self, blk, h):
        T, H, nkv = h.shape[0], self.hidden, self.n_kv * self.head_dim
        qkv = common.mm(h, blk["w_qkv"])
        k = qkv[:, H:H + nkv].reshape(T, self.n_groups, -1)
        v = qkv[:, H + nkv:].reshape(T, self.n_groups, -1)
        return self._queries(qkv[:, :H]), k.astype(self.dtype), \
            v.astype(self.dtype)

    def _diff_out(self, blk, lam0, a):
        """Attention rows ``a`` (T, key pairs, rows, 2d) -> the layer's
        output (T, hidden) after ``W_o``."""
        T = a.shape[0]
        a = a.reshape(T, self.n_groups, self.rows // 2, 2, -1)
        lam = jnp.exp(jnp.sum(blk["lq1"] * blk["lk1"])) \
            - jnp.exp(jnp.sum(blk["lq2"] * blk["lk2"])) + lam0
        o = common.rms(blk["subln"], a[..., 0, :] - lam * a[..., 1, :],
                       self.eps) * (1.0 - lam0)
        return common.mm(o.reshape(T, -1), blk["w_o"])

    def _attend_rows(self, q4, k, v, allowed, first, last, key_chunk,
                     n_chunks):
        """Query rows ``q4`` (Q, key pairs, rows, 2d) over keys ``k`` / ``v``
        (S, key pairs, 2d), ``_attend_loop``'s chunks from ``first``;
        ``allowed(kp)`` (Q, chunk) is a query's mask.  Returns q4's shape."""
        Q, G, J, dd = q4.shape
        o = _attend_loop(
            q4.transpose(0, 2, 1, 3).reshape(Q * J, G, dd), k, v,
            lambda kp: jnp.repeat(allowed(kp), J, axis=0)[None], first, last,
            dd ** -0.5, self.dtype, key_chunk, n_chunks)
        return o.reshape(Q, J, G, dd).transpose(0, 2, 1, 3)

    # -- prefill: one sequence, a pair of layers and a chunk at a time -------
    def _mamba_row(self, blk, xc, starts, length, rows):
        """One Mamba layer over one sequence ``xc`` (chunks, P, H) of which
        ``length`` positions are real, ``(conv, ssm)`` carried from chunk to
        chunk.  Returns ``(xc, (conv, ssm), y[rows])``: the state after the
        last real position and the gated output at positions ``rows``."""
        NC, P, _ = xc.shape
        Di, N, R = self.d_inner, self.d_state, self.dt_rank
        A = -jnp.exp(blk["A_log"]).T                              # (N, Di)

        def live(conv, s, x, start):
            pos = start + jnp.arange(P)
            zu = common.mm(self._ln(blk, "ln1", x), blk["w_in"])
            with _scope("ssm_conv"):
                u, conv = sscan.conv_chunk(
                    zu[:, :Di], blk["conv_w"], blk["conv_b"], conv,
                    jnp.clip(length - start, 0, P))
            u = jax.nn.silu(u)
            dbc = common.mm(u, blk["w_x"])
            delta = jax.nn.softplus(common.mm(dbc[:, :R], blk["w_dt"])
                                    + blk["b_dt"])
            # a padding position neither enters the state nor decays it
            delta = jnp.where((pos < length)[:, None], delta, 0.0)
            with _scope("ssm_scan"):
                y, s = sscan.scan_chunk(u, delta, A, dbc[:, R:R + N],
                                        dbc[:, R + N:], s,
                                        unroll=_SCAN_UNROLL)
            y = (y + blk["D"] * u) * jax.nn.silu(zu[:, Di:])
            h = x + common.mm(y, blk["w_out"])
            return (conv, s), (h + self._mlp(blk, h), y)

        def chunk(carry, inp):
            # (a chunk of padding alone is not computed)
            return jax.lax.cond(
                inp[1] < length, live,
                lambda conv, s, x, start: ((conv, s), (
                    x, jnp.zeros((P, Di), jnp.float32))), *carry, *inp)

        state = (jnp.zeros((self.d_conv - 1, Di), jnp.float32),
                 jnp.zeros((N, Di), jnp.float32))
        state, (xc, y) = jax.lax.scan(chunk, state, (xc, starts))
        return xc, state, jnp.take(y.reshape(NC * P, Di), rows, axis=0)

    def _attn_row(self, blk, lam0, win, xc, starts, length):
        """One attention layer, a window layer where ``win`` (traced), over
        one sequence ``xc`` (chunks, P, H).  Returns ``(xc, (k, v))``: the
        sequence's keys and values (S, key pairs, 2d) in the cache's type."""
        NC, P, _ = xc.shape
        S, G, J, dd, dt = NC * P, self.n_groups, self.rows, \
            2 * self.head_dim, self.dtype
        qb = min(_QUERY_BLOCK, P)
        if P % qb:
            raise ValueError(f"prefill chunk {P} is no multiple of {qb}")
        # a window block reads the ``wspan`` keys that end with it (whole
        # chunks of ``wkc``), a full block every key up to it
        wkc = min(_WINDOW_CHUNK, S)
        wspan = min(S, -(-(self.window + qb) // wkc) * wkc)

        def qkv(_, inp):
            return None, jax.lax.cond(
                inp[1] < length,
                lambda x, start: self._qkv(blk, self._ln(blk, "ln1", x)),
                lambda x, start: (jnp.zeros((P, G, J, dd), jnp.float32),
                                  jnp.zeros((P, G, dd), dt),
                                  jnp.zeros((P, G, dd), dt)), *inp)

        _, (q4, k, v) = jax.lax.scan(qkv, None, (xc, starts))
        k, v = k.reshape(S, G, dd), v.reshape(S, G, dd)

        def attender(window):
            span, kc = (wspan, wkc) if window else (S, min(_KEY_CHUNK, S))

            def attend(q, t):
                lo = jnp.maximum(t[0] + qb - wspan, 0) if window else 0

                def allowed(kp):
                    m = kp[None, :] <= t[:, None]
                    if window:
                        m &= kp[None, :] > t[:, None] - self.window
                    return m

                with _scope("window_attend" if window else "diff_attend"):
                    return self._attend_rows(q, k, v, allowed, lo, t[-1], kc,
                                             span // kc)
            return attend

        def block(args):
            q, t = args
            # a block past the row's length holds no real query: not run
            return jax.lax.switch(
                jnp.where(t[0] < length, 1 + win.astype(jnp.int32), 0),
                [lambda q, t: jnp.zeros((qb, G, J, dd), jnp.float32),
                 attender(False), attender(True)], q, t)

        def live(x, q, start):
            pos = start + jnp.arange(P)
            a = jax.lax.map(block, (q.reshape(P // qb, qb, G, J, dd),
                                    pos.reshape(P // qb, qb)))
            h = x + self._diff_out(blk, lam0, a.reshape(P, G, J, dd))
            return h + self._mlp(blk, h)

        def chunk(_, inp):
            return None, jax.lax.cond(inp[2] < length, live,
                                      lambda x, q, start: x, *inp)

        _, xc = jax.lax.scan(chunk, None, (xc, q4, starts))
        return xc, (k, v)

    def _cross_decoder(self, params, x, m, k, v, rows):
        """The layers past the full layer over the positions ``rows`` (Q,):
        ``x`` (Q, H) their residual, ``m`` (Q, d_inner) their memory, ``k``
        / ``v`` (S, key pairs, 2d) the full layer's keys and values.  A
        ``lax.scan`` over (GMU, cross) pairs."""
        S = k.shape[0]
        kc = min(_KEY_CHUNK, S)

        def pair(x, inp):
            g, c, lam0 = inp
            with _scope("gmu"):
                x = x + common.mm(m * jax.nn.silu(common.mm(
                    self._ln(g, "ln1", x), g["w_g"])), g["w_o"])
            x = x + self._mlp(g, x)
            q4 = self._queries(common.mm(self._ln(c, "ln1", x), c["w_q"]))
            with _scope("cross_attend"):
                a = self._attend_rows(
                    q4, k, v, lambda kp: kp[None, :] <= rows[:, None], 0,
                    rows.max(), kc, S // kc)
            x = x + self._diff_out(c, lam0, a)
            return x + self._mlp(c, x), None

        lam = jnp.asarray([lambda_init(l) for l in self.cross_ids])
        x, _ = jax.lax.scan(pair, x, (params["gmu"], params["cross"], lam))
        return x

    def _forward_row(self, params, ids, length, every: bool):
        """One sequence through the stack: ``ids`` (S,) right-padded tokens
        of which ``length`` are real.  The cross-decoder runs over every
        position where ``every``, else over the last real one.  Returns
        ``(x (Q, H), (k, v), (conv, ssm), counts)``: the last layer's output
        at those positions, each attention layer's keys and values
        (self-decoder pairs, S, key pairs, 2d), each Mamba layer's state
        after the last real position."""
        S = ids.shape[0]
        P = min(_POS_CHUNK, S)
        if S % P:
            raise ValueError(f"prefill length {S} is no multiple of {P}")
        xc = self._embed(params, ids).reshape(S // P, P, self.hidden)
        starts = jnp.arange(S // P) * P
        rows = jnp.arange(S) if every \
            else jnp.maximum(length - 1, 0)[None].astype(jnp.int32)
        lam = jnp.asarray([lambda_init(l) for l in self.attn_ids])
        windowed = jnp.arange(self.n_pairs) < self.n_window

        def pair(xc, inp):
            bm, ba, lam0, win = inp
            xc, state, m = self._mamba_row(bm, xc, starts, length, rows)
            xc, kv = self._attn_row(ba, lam0, win, xc, starts, length)
            return xc, (state, m, kv)

        xc, (state, m, (k, v)) = jax.lax.scan(
            pair, xc, (params["mamba"], params["attn"], lam, windowed))
        x = jnp.take(xc.reshape(S, self.hidden), rows, axis=0)
        x = self._cross_decoder(params, x, m[-1], k[-1], v[-1], rows)
        live = jnp.minimum(-(-length // P), S // P)
        counts = _count(
            prefill_positions_real=length,
            prefill_cross_positions=rows.shape[0] * 2 * self.n_cross,
            ssm_positions_scanned=self.n_pairs * live * P,
            ssm_positions_real=self.n_pairs * length)
        return x, (k, v), state, counts

    def call(self, params, inputs, *, training=False, rng=None):
        """Teacher-forced logits (B, T, V), a sequence at a time, the
        cross-decoder over every position."""
        def row(seq):
            x, _, _, _ = self._forward_row(params, seq, seq.shape[0], True)
            return self._logits(params, x)

        return jax.lax.map(row, common.ids(inputs))

    # -- decode: one token a row ---------------------------------------------
    def _attend_keys(self, q4, k, v, ok):
        """One token a row over gathered keys: ``q4`` (A, key pairs, rows,
        2d), ``k`` / ``v`` (A, key pairs, S, 2d), ``ok`` (A, S)."""
        att = common.ein("agjd,agsd->agjs", q4, k, self.dtype) \
            * q4.shape[-1] ** -0.5
        att = jnp.where(ok[:, None, None], att, NEG_INF)
        e = jnp.exp(att - att.max(-1, keepdims=True))
        return common.ein("agjs,agsd->agjd", e, v, self.dtype) \
            / e.sum(-1)[..., None]

    def _page_attend(self, q4, k_pool, v_pool, bt, pos, active, bl, mode):
        """The full layer's pages, read by layer F and the cross layers: the
        grouped-page kernel, or (``xla``) every row's whole table gathered;
        an idle slot reads zero either way."""
        if mode != "xla":
            return paged.grouped_paged_attention(
                q4, k_pool, v_pool, bt, pos + 1,
                interpret=mode == "interpret")
        A, G = q4.shape[:2]
        blocks = jnp.broadcast_to(bt[:, None], (A, G, bt.shape[1]))
        kk = paged.grouped_blocks(k_pool, blocks).reshape(A, G, -1, q4.shape[-1])
        vv = paged.grouped_blocks(v_pool, blocks).reshape(A, G, -1, q4.shape[-1])
        ok = jnp.arange(kk.shape[2])[None, :] <= pos[:, None]
        out = self._attend_keys(q4, kk, vv, ok)
        return jnp.where(active[:, None, None, None], out, 0.0)

    def _mamba_step(self, blk, h, conv, ssm, active):
        """One token a row through a Mamba layer: ``(out, y, conv, ssm)``;
        an idle slot's state is left as it was."""
        Di, N, R = self.d_inner, self.d_state, self.dt_rank
        zu = common.mm(h, blk["w_in"])
        with _scope("ssm_conv"):
            u, conv2 = sscan.conv_step(zu[:, :Di], blk["conv_w"],
                                       blk["conv_b"], conv)
        u = jax.nn.silu(u)
        dbc = common.mm(u, blk["w_x"])
        delta = jax.nn.softplus(common.mm(dbc[:, :R], blk["w_dt"])
                                + blk["b_dt"])
        with _scope("ssm_step"):
            y, s2 = sscan.scan_step(u, delta, -jnp.exp(blk["A_log"]).T,
                                    dbc[:, R:R + N], dbc[:, R + N:], ssm)
        y = (y + blk["D"] * u) * jax.nn.silu(zu[:, Di:])
        keep = active[:, None, None]
        return common.mm(y, blk["w_out"]), y, \
            jnp.where(keep, conv2, conv).astype(conv.dtype), \
            jnp.where(keep, s2, ssm).astype(ssm.dtype)

    def decode_paged(self, params, state, block_tables, pos, tokens, *,
                     block_len: int, kv_quant: str = "off", impl=None):
        """One token a row (the contract's decode step), every layer
        unrolled.  ``impl`` (``ops/dispatch.resolve_impl``) picks the page
        read: the grouped-page kernel (``pallas`` on a TPU, ``interpret``)
        or the XLA gather (``xla``, a CPU's).  An idle slot (table all
        trash) changes no state of its own.  Returns ``(logits, state)``."""
        mode = resolve_impl(impl)
        bl = int(block_len)
        bt = jnp.asarray(block_tables, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        cursor = paged.pool_cursor(bt, pos, bl)
        active = bt[:, 0] != 0
        x = self._embed(params, jnp.asarray(tokens, jnp.int32))
        new = {name: list(state[name])
               for name in ("k", "v", "rk", "rv", "conv", "ssm")}
        m = None
        for l, kind in enumerate(self.kinds):
            i = self.index[l]
            blk = jax.tree.map(lambda a: a[i], params[_GROUP[kind]])
            h = self._ln(blk, "ln1", x)
            if kind == MAMBA:
                out, y, new["conv"][i], new["ssm"][i] = self._mamba_step(
                    blk, h, new["conv"][i], new["ssm"][i], active)
                m = y                    # the last Mamba layer's is kept
            elif kind == GMU:
                with _scope("gmu"):
                    out = common.mm(m * jax.nn.silu(common.mm(h, blk["w_g"])),
                                    blk["w_o"])
            else:
                if kind == CROSS:
                    q4 = self._queries(common.mm(h, blk["w_q"]))
                else:
                    q4, k, v = self._qkv(blk, h)
                if kind == WINDOW:
                    with _scope("ring_put"):
                        new["rk"][i] = paged.ring_put(new["rk"][i], k, pos,
                                                      active)
                        new["rv"][i] = paged.ring_put(new["rv"][i], v, pos,
                                                      active)
                    with _scope("window_attend"):
                        a = self._attend_keys(
                            q4, new["rk"][i], new["rv"][i],
                            jnp.arange(self.window)[None, :] <= pos[:, None])
                else:
                    if kind == FULL:
                        new["k"][0], new["v"][0] = paged.grouped_append(
                            state, 0, k, v, cursor)
                    with _scope("diff_attend" if kind == FULL
                                else "cross_attend"):
                        a = self._page_attend(q4, new["k"][0], new["v"][0],
                                              bt, pos, active, bl, mode)
                out = self._diff_out(blk, lambda_init(l), a)
            x = x + out
            x = x + self._mlp(blk, x)
        n_table, readers = bt.shape[1], 1 + self.n_cross
        read = n_table * bl if mode == "xla" \
            else jnp.minimum(-(-(pos + 1) // bl), n_table) * bl
        ctx = jnp.where(active, pos + 1, 0).sum()
        counts = _count(
            window_keys_attended=jnp.where(
                active, jnp.minimum(pos + 1, self.window), 0).sum()
            * self.n_window,
            window_keys_context=ctx * self.n_window,
            full_keys_read=jnp.where(active, read, 0).sum() * readers,
            full_keys_context=ctx * readers)
        return self._logits(params, x), dict(
            state, **new, counters=common.bump(state["counters"], counts))

    # -- the paged contract ---------------------------------------------------
    def init_paged_pools(self, n_blocks: int, block_len: int,
                         max_active: int, kv_quant: str = "off"):
        """Zeroed state: ``ops/paged_attention``'s window format (one full
        layer's pages, the window layers' rings; 2d-wide key pairs), each
        Mamba layer's ``conv`` and ``ssm`` slots, and the counters."""
        if kv_quant != "off":
            raise ValueError("the window format has no quantised form")
        sd = self.state_dtype
        return dict(
            paged.init_window_pools(1, self.n_window, n_blocks, block_len,
                                    self.n_groups, 2 * self.head_dim,
                                    self.window, max_active, self.dtype),
            conv=[np.zeros((max_active, self.d_conv - 1, self.d_inner), sd)
                  for _ in range(self.n_pairs)],
            ssm=[np.zeros((max_active, self.d_state, self.d_inner), sd)
                 for _ in range(self.n_pairs)],
            counters=np.zeros((len(COUNTERS), 2), np.int32))

    def paged_state_bytes(self, state):
        out = paged.pool_bytes({k: state[k] for k in ("k", "v", "rk", "rv")})
        out["lanes"] += sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                            for a in state["conv"] + state["ssm"]) \
            + int(np.prod(state["counters"].shape)) * 4
        return out

    def paged_counters(self, state):
        return common.read_counters(state["counters"], COUNTERS)

    def prefill_paged(self, params, state, prompt, lengths, dest, slots, *,
                      block_len: int, kv_quant: str = "off"):
        """Rows in sequence inside ONE program (``lax.scan`` carries the
        state), so a batch's temporaries are one row's; a batch's padding
        row (its blocks all trash) is skipped whole.  A row's full layer's
        K/V land in its ``dest`` blocks, its window layers' last ``window``
        positions in its slot's rings, its Mamba layers' state in its
        slot's ``conv`` / ``ssm``."""
        xs = (common.ids(prompt), jnp.asarray(lengths, jnp.int32),
              jnp.asarray(dest, jnp.int32), jnp.asarray(slots, jnp.int32))

        def put(leaves, rows, slot):
            return [jax.lax.dynamic_update_slice(
                a, r[None].astype(a.dtype), (slot, 0, 0))
                for a, r in zip(leaves, rows)]

        def run(st, ids, n, dst, slot):
            x, (k, v), (conv, ssm), counts = self._forward_row(
                params, ids, n, False)
            ks, vs, _ = paged.grouped_commit(st, [k[-1]], [v[-1]], [], dst,
                                             block_len=block_len)
            with _scope("ring_put"):
                rk = [paged.ring_commit(r, k[i], n, slot)
                      for i, r in enumerate(st["rk"])]
                rv = [paged.ring_commit(r, v[i], n, slot)
                      for i, r in enumerate(st["rv"])]
            st = dict(st, k=ks, v=vs, rk=rk, rv=rv,
                      conv=put(st["conv"], conv, slot),
                      ssm=put(st["ssm"], ssm, slot),
                      counters=common.bump(st["counters"], counts))
            return st, x[0]

        def skip(st, ids, n, dst, slot):
            return st, jnp.zeros((self.hidden,), jnp.float32)

        def row(st, x):
            return jax.lax.cond(x[2][0] != 0, run, skip, st, *x)

        # the head once a call, outside the rows' loop
        state, last = jax.lax.scan(row, state, xs)
        return state, self._logits(params, last)

    def prefill_shared_paged(self, params, state, suffix, lengths,
                             prefix_len, ptab, dest, slots, *,
                             block_len: int, kv_quant: str = "off"):
        raise NotImplementedError(
            "SSMHybridLM cannot prefill behind a shared prefix: its Mamba "
            "state and window rings at the prefix's end are per-slot state "
            "that no resident block holds; serve it with "
            "generation.prefix_cache=false")

    # -- contiguous caches: not offered ---------------------------------------
    def init_decode(self, params, prompt, lengths=None,
                    cache_len: Optional[int] = None):
        raise NotImplementedError(
            "SSMHybridLM is served through the paged contract only "
            "(generation.paged=true)")

    def decode_step(self, params, state, tokens):
        raise NotImplementedError(
            "SSMHybridLM is served through the paged contract only "
            "(generation.paged=true)")
