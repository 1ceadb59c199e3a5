"""A decoder whose grouped-query attention layers are of two kinds, WINDOW
layers (rotary, the last ``sliding_window_size`` positions) and FULL layers
(no rotary, the whole context), each followed by a routed expert layer whose
router reads the LAYER'S INPUT.

``WindowMoELM`` is the block of the SmallThinker family (``rope_layout`` /
``sliding_window_layout`` name each layer, ``moe_*`` the experts), served
through the same paged contract as ``models/textmodels.TransformerLM`` (its
docstring is the contract's text).  Layer l acts on the float32 residual
``x``:

- ``r = x W_r`` (experts wide, float32): the router, BEFORE attention, on the
  un-normalised input;
- ``h = RMSNorm(x)``; ``q = h W_q`` (heads x d), ``k, v = h W_k, h W_v`` (kv
  heads x d); query head n reads key head ``n // (heads / kv heads)``; a window
  layer turns ``q`` and ``k`` by half-split rotary at absolute positions and
  lets query i attend keys ``i - window < j <= i``; a full layer has no rotary
  and attends ``j <= i``; ``x += softmax(q k^T / sqrt(d)) v W_o``;
- ``u = RMSNorm(x)``; the ``moe_num_active_primary_experts`` largest of
  ``softmax(r)``, renormalised (``norm_topk_prob``: the softmax over the chosen
  logits), weigh ``relu(u W_gate,e) * (u W_up,e) W_down,e`` (ReGLU); no shared
  expert; ``x += y`` (``lm_common.routed_experts``, every expert held);
- ``logits = RMSNorm(x) W_head``, untied.

The state the scheduler carries for the class (one opaque pytree) is
``ops/paged_attention``'s window format: the full layers' K/V in grouped-query
pages read through the block table, each window layer's K/V in a per-slot ring
of the last ``window`` positions (a slot's own, like ``SparseLinearLM``'s
recurrent state, so ``paged_prefix_sharing`` is False), and ``counters``.

Prefill runs a batch's rows in a ``lax.scan`` (a padding row is skipped
whole), a layer and ``_POS_CHUNK`` positions at a time (a chunk of padding
alone is not computed), queries in blocks of ``_QUERY_BLOCK`` (a block past
the row's length does not run).  A window layer's query block reads only the
keys of its window (a slice of ``window + _QUERY_BLOCK`` keys, in chunks of
``_WINDOW_CHUNK``): O(n window), not O(n^2).  The layers run in a
``lax.scan`` (each picks its weights by index, a copy of one layer's
weights a row, and its kind by a flag) and a block's key chunks in a
``lax.fori_loop``, so that a prefill program holds one layer's code and one
chunk's, whatever the depth and the bucket: a serving start lowers and
compiles one prefill program a batch and prompt bucket, each in a fraction
of the time unrolled layers took.  Decode reads a window layer's ring in
chunks, each skipped when it lies past every active row's context.  A full
layer's pages are read on a TPU by ``ops/paged_attention``'s grouped-page
kernel, which folds each row's own live blocks and no others, so that a
row's bytes follow its own length; the XLA path (a CPU, and the kernel's
oracle) gathers the pages for every row in chunks of table entries, a chunk
skipped past every active row's context, so that it reads each row to the
longest live row.  Weights are built in ``dtype`` (bfloat16 as served;
the router and the norms float32), so ``matmul_operands`` is the tree itself.

Not built: sharing a resident prefix (``prefill_shared_paged`` raises: the
rings would have to be rebuilt from the prefix's pages), contiguous caches
(``init_decode`` / ``decode_step`` raise: the class is served paged), the
family's secondary experts (the configuration gives none).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.models import lm_common as common
from analytics_zoo_tpu.models.lm_common import NEG_INF, scope as _scope
from analytics_zoo_tpu.nn.module import Layer
from analytics_zoo_tpu.ops import paged_attention as paged
from analytics_zoo_tpu.ops.dispatch import resolve_impl

_POS_CHUNK = 2048       # prefill positions a layer takes at once
_QUERY_BLOCK = 256      # ... of which the attention takes this many queries
_KEY_CHUNK = 2048       # a full layer's keys a block takes at once
_WINDOW_CHUNK = 256     # a window layer's keys a block takes at once
_DECODE_CHUNK = 2048    # decode keys at once (ring rows, or pages' positions)
_PAIR_SLAB = 2048       # token-expert pairs one grouped matmul takes

# What the programs count, in the order of the state's ``counters`` leaf.
# ``moe_*`` over every expert-layer call of every program, real tokens only,
# except the two marked (decode); ``window_keys_*`` over decode rows (idle
# slots left out) x window layers; ``prefill_window_chunks*`` over the live
# query blocks of window layers (a prefill row's padding rows and blocks run
# nothing and count nothing); ``full_keys_*`` over decode rows (idle slots
# left out) x full layers.
COUNTERS = (
    "moe_pairs",                  # token-expert pairs routed
    "moe_pairs_busiest",          # ... on the busiest expert, summed a call
    "moe_experts_touched",        # (decode) experts with >= 1 pair, summed
    "moe_layer_steps",            # (decode) expert-layer calls
    "window_keys_attended",       # keys a window layer read: min(ctx, window)
    "window_keys_context",        # keys in context there
    "prefill_window_chunks",      # key chunks a causal square would run
    "prefill_window_chunks_run",  # ... those that met the block's window
    "full_keys_read",             # positions a full layer's read fetched
    "full_keys_context",          # keys in context there: pos + 1
)


def _no_counts():
    return common.no_counts(COUNTERS)


def _count(**named):
    return common.counts(COUNTERS, **named)


def _attend_loop(q, k, v, allowed, first, last, scale, dtype,
                 key_chunk: int, n_chunks: int):
    """Softmax attention of queries ``q`` (Q, heads, d) over the keys
    ``first .. first + n_chunks * key_chunk - 1`` of ``k`` / ``v`` (S, heads,
    d), a chunk of ``key_chunk`` at a time in a ``lax.fori_loop`` (one
    chunk's code, whatever the span), with ``lm_common.attend_chunks``'s
    arithmetic: each chunk's maximum, sum and output, merged in order
    under the largest maximum.  ``allowed(kp)`` gives the mask (anything
    that broadcasts to (heads, Q, key_chunk)) of the chunk's key positions
    ``kp``.  The chunks whose keys all lie after the queries' last position
    ``last`` do not run.  Returns (Q, heads * d_v)."""
    hq = (q.shape[1], q.shape[0])

    def chunk(c, parts):
        lo = first + c * key_chunk
        ks = jax.lax.dynamic_slice_in_dim(k, lo, key_chunk)
        vs = jax.lax.dynamic_slice_in_dim(v, lo, key_chunk)
        att = common.ein("qhd,shd->hqs", q, ks, dtype) * scale
        att = jnp.where(allowed(lo + jnp.arange(key_chunk)), att, NEG_INF)
        m = att.max(-1)
        e = jnp.exp(att - m[..., None]).astype(dtype)
        return tuple(p.at[c].set(x) for p, x in zip(parts, (
            m, e.sum(-1, dtype=jnp.float32),
            common.ein("hqs,shd->hqd", e, vs, dtype))))

    n = jnp.minimum(n_chunks, (last - first) // key_chunk + 1)
    m, s, o = jax.lax.fori_loop(0, n, chunk, (
        jnp.full((n_chunks,) + hq, NEG_INF, jnp.float32),
        jnp.zeros((n_chunks,) + hq, jnp.float32),
        jnp.zeros((n_chunks,) + hq + (v.shape[-1],), jnp.float32)))
    top = m.max(0)
    # the merge written out a chunk at a time, as ``attend_chunks`` writes
    # it (a chunk that did not run adds exactly 0)
    den = sum(jnp.exp(m[c] - top) * s[c] for c in range(n_chunks))
    out = sum(jnp.exp(m[c] - top)[..., None] * o[c] for c in range(n_chunks))
    return (out / den[..., None]).transpose(1, 0, 2).reshape(q.shape[0], -1)


class WindowMoELM(Layer):
    """See the module docstring.  Constructor arguments carry the names of
    the published ``config.json``; ``from_config`` reads one."""

    # the scheduler refuses ``prefix_cache`` over this class at start
    paged_prefix_sharing = False

    def __init__(self, vocab_size: int, hidden_size: int,
                 num_hidden_layers: int, num_attention_heads: int,
                 num_key_value_heads: int, head_dim: int,
                 moe_ffn_hidden_size: int, moe_num_primary_experts: int,
                 moe_num_active_primary_experts: int,
                 rope_layout: Sequence[int],
                 sliding_window_layout: Sequence[int],
                 sliding_window_size: int,
                 moe_primary_router_apply_softmax: bool = True,
                 norm_topk_prob: bool = True, rope_theta: float = 1e4,
                 rms_norm_eps: float = 1e-6, dtype: str = "bfloat16",
                 initializer_range: float = 0.02,
                 **kwargs):
        super().__init__(**kwargs)
        self.vocab_size = int(vocab_size)
        self.hidden = int(hidden_size)
        self.n_layers = int(num_hidden_layers)
        self.n_head, self.n_kv = int(num_attention_heads), \
            int(num_key_value_heads)
        self.head_dim = int(head_dim)
        if self.n_head % self.n_kv:
            raise ValueError(f"{self.n_head} query heads over {self.n_kv} "
                             f"key heads")
        self.group = self.n_head // self.n_kv
        self.expert_width = int(moe_ffn_hidden_size)
        self.n_experts = int(moe_num_primary_experts)
        self.top_k = int(moe_num_active_primary_experts)
        if not (moe_primary_router_apply_softmax and norm_topk_prob):
            raise ValueError(
                "the router is served as a softmax over the top-k chosen "
                "(moe_primary_router_apply_softmax and norm_topk_prob true)")
        rope, slide = tuple(map(int, rope_layout)), \
            tuple(map(int, sliding_window_layout))
        if len(rope) != self.n_layers or len(slide) != self.n_layers \
                or not set(rope) | set(slide) <= {0, 1}:
            raise ValueError(f"rope_layout and sliding_window_layout must "
                             f"give 0 or 1 for each of {self.n_layers} "
                             f"layers")
        if rope != slide:
            odd = [i for i, (a, b) in enumerate(zip(rope, slide)) if a != b]
            raise ValueError(
                f"layers {odd}: this class serves window layers with rotary "
                f"and full layers without (rope_layout == "
                f"sliding_window_layout)")
        self.windowed = tuple(bool(s) for s in slide)
        self.full_ids = [i for i, w in enumerate(self.windowed) if not w]
        self.window_ids = [i for i, w in enumerate(self.windowed) if w]
        self.window = int(sliding_window_size)
        if self.window_ids and self.window < 1:
            raise ValueError(f"sliding_window_size={self.window}")
        self.theta, self.eps = float(rope_theta), float(rms_norm_eps)
        self.dtype = jnp.dtype(dtype)
        self.std = float(initializer_range)
        self._declared_input_shape = (None,)

    @classmethod
    def from_config(cls, cfg: dict, **overrides) -> "WindowMoELM":
        """From a published ``config.json`` as a configuration file cuts it:
        ``num_hidden_layers`` and the two layouts are the layers held here.
        Keys the class does not know are not read."""
        import inspect
        known = set(inspect.signature(cls.__init__).parameters) - {"self"}
        kw = {k: v for k, v in cfg.items() if k in known}
        kw.update(overrides)
        return cls(**kw)

    # -- weights --------------------------------------------------------------
    def build(self, rng, input_shape=None):
        """Random weights from ``rng`` (normal, ``initializer_range``), in
        ``dtype``; norm gains 1 + 0.1 n; the router and every norm in
        float32.  ``blocks[l]`` holds layer l's weights, its experts stacked
        on a leading axis (``w_gate`` / ``w_up`` (experts, H, F), ``w_down``
        (experts, F, H))."""
        H, F, E, dt, std = self.hidden, self.expert_width, self.n_experts, \
            self.dtype, self.std
        nq, nkv = self.n_head * self.head_dim, self.n_kv * self.head_dim
        keys = iter(jax.random.split(rng, 3 + 10 * self.n_layers))

        def w(*shape, dtype=dt, scale=std):
            return (scale * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dtype)

        def gain(n):
            return 1.0 + w(n, dtype=jnp.float32, scale=0.1)

        blocks = [{"ln1": gain(H), "ln2": gain(H),
                   "router": w(H, E, dtype=jnp.float32),
                   "q": w(H, nq), "k": w(H, nkv), "v": w(H, nkv),
                   "o": w(nq, H), "w_gate": w(E, H, F), "w_up": w(E, H, F),
                   "w_down": w(E, F, H)} for _ in range(self.n_layers)]
        return {"embed": w(self.vocab_size, H), "ln_f": gain(H),
                "head": w(H, self.vocab_size), "blocks": blocks}

    def matmul_operands(self, params, dtype):
        """The tree is built in its operand type: nothing to round."""
        return params

    # -- shared pieces --------------------------------------------------------
    def _rms(self, g, x):
        return common.rms(g, x, self.eps)

    def _embed(self, params, ids):
        return jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)

    def _logits(self, params, h):
        return common.mm(self._rms(params["ln_f"], h), params["head"])

    def _qkv(self, blk, u, pos, win):
        """Queries (T, heads, d), keys and values (T, kv heads, d) of tokens
        ``u`` (T, H) at ``pos`` (T,), turned where ``win`` (a window layer).
        Where ``win`` is traced, a full layer turns by angle 0, which leaves
        its queries and keys as they are."""
        T, d = u.shape[0], self.head_dim
        q = common.mm(u, blk["q"]).reshape(T, self.n_head, d)
        k = common.mm(u, blk["k"]).reshape(T, self.n_kv, d)
        v = common.mm(u, blk["v"]).reshape(T, self.n_kv, d)
        if isinstance(win, bool):
            if not win:
                return q, k, v
        else:
            pos = jnp.where(win, pos, 0)
        return common.rotary_half(q, pos, self.theta), \
            common.rotary_half(k, pos, self.theta), v

    def _experts(self, blk, x, r_in, valid, decode: bool, impl=None):
        """The expert layer after attention: ``x`` (T, H) the residual,
        ``r_in`` the layer's input the router reads; ``impl`` as
        ``lm_common.routed_experts`` takes it.  Returns ``(y, counts)``."""
        y, c = common.routed_experts(
            self._rms(blk["ln2"], x), r_in, blk, valid, top_k=self.top_k,
            held=(0, self.n_experts), dtype=self.dtype, scoring="softmax",
            act=jax.nn.relu, slab=_PAIR_SLAB, impl=impl)
        step = jnp.int32(1 if decode else 0)
        return y, _count(moe_pairs=c["pairs"], moe_pairs_busiest=c["busiest"],
                         moe_experts_touched=step * c["touched"],
                         moe_layer_steps=step)

    # -- prefill: one sequence, a layer and a chunk of positions at a time ----
    def _layer_row(self, blk, win, xc, starts, length):
        """One layer, a window layer where ``win`` (traced), over one
        sequence ``xc`` (chunks, P, H), of which ``length`` positions are
        real.  Returns ``(xc, (k, v), counts)``: the sequence's keys and
        values (S, kv heads, d) in the cache's type."""
        NC, P, _ = xc.shape
        S, G, J, d, dt = NC * P, self.n_kv, self.group, self.head_dim, \
            self.dtype
        qb = min(_QUERY_BLOCK, P)
        if P % qb:
            raise ValueError(f"prefill chunk {P} is no multiple of {qb}")
        scale = d ** -0.5
        # a window block reads the ``wspan`` keys that end with it (whole
        # chunks of ``wkc``: S is a multiple of one), a full block every key
        # up to it
        wkc = min(_WINDOW_CHUNK, S)
        wspan = min(S, -(-(self.window + qb) // wkc) * wkc)

        def kv(_, inp):
            def live(x, start):
                _, k, v = self._qkv(blk, self._rms(blk["ln1"], x),
                                    start + jnp.arange(P), win)
                return k.astype(dt), v.astype(dt)

            # (a chunk of padding alone is not computed: no real query
            # attends its keys)
            return None, jax.lax.cond(
                inp[1] < length, live,
                lambda x, start: (jnp.zeros((P, G, d), dt),) * 2, *inp)

        _, (k, v) = jax.lax.scan(kv, None, (xc, starts))
        k, v = k.reshape(S, G, d), v.reshape(S, G, d)

        def window_first(t0):
            # where a window block's keys begin
            return jnp.maximum(t0 + qb - wspan, 0)

        def attender(window):
            span, kc = (wspan, wkc) if window else \
                (S, min(_KEY_CHUNK, S))

            def attend(q, t):
                lo = window_first(t[0]) if window else 0

                def allowed(kp):
                    m = kp[None, :] <= t[:, None]
                    if window:
                        m &= kp[None, :] > t[:, None] - self.window
                    # a query's J heads are J rows of the folded query axis
                    return jnp.repeat(m, J, axis=0)[None]

                with _scope("window_attend" if window else "full_attend"):
                    o = _attend_loop(
                        q.transpose(0, 2, 1, 3).reshape(qb * J, G, d), k, v,
                        allowed, lo, t[-1], scale, dt, kc, span // kc)
                return o.reshape(qb, J, G, d).transpose(0, 2, 1, 3).reshape(
                    qb, G * J * d)
            return attend

        def block(args):
            q, t = args
            # a block past the row's length holds no real query: not run
            return jax.lax.switch(
                jnp.where(t[0] < length, 1 + win.astype(jnp.int32), 0),
                [lambda q, t: jnp.zeros((qb, G * J * d), jnp.float32),
                 attender(False), attender(True)], q, t)

        def chunk_counts(start):
            # key chunks of a window layer's live blocks: what a causal
            # square runs, and what ran (the chunks ``_attend_loop`` runs)
            t0 = start + jnp.arange(P // qb) * qb
            live = win & (t0 < length)
            square = (t0 + qb - 1) // wkc + 1
            firsts = window_first(t0)[:, None] + jnp.arange(0, wspan, wkc)
            ran = (firsts <= (t0 + qb - 1)[:, None]).sum(-1)
            return _count(
                prefill_window_chunks=jnp.where(live, square, 0).sum(),
                prefill_window_chunks_run=jnp.where(live, ran, 0).sum())

        def live(x, start):
            pos = start + jnp.arange(P)
            u = self._rms(blk["ln1"], x)
            q, _, _ = self._qkv(blk, u, pos, win)
            o = jax.lax.map(block, (q.reshape(P // qb, qb, G, J, d),
                                    pos.reshape(P // qb, qb)))
            h = x + common.mm(o.reshape(P, -1), blk["o"])
            y, c = self._experts(blk, h, x, pos < length, decode=False)
            return h + y, c + chunk_counts(start)

        def chunk(_, inp):
            return None, jax.lax.cond(
                inp[1] < length, live,
                lambda x, start: (x, _no_counts()), *inp)

        _, (xc, counts) = jax.lax.scan(chunk, None, (xc, starts))
        return xc, (k, v), counts.sum(0)

    def _forward_row(self, params, ids, length):
        """One sequence through the stack: ``ids`` (S,) right-padded tokens
        of which ``length`` are real.  Returns ``(x (S, H), (k, v),
        counts)``: the last layer's output, every layer's keys and values
        (layers, S, kv heads, d) and the counters' increments.  The layers
        run in a ``lax.scan``, each picking its weights by index (a copy of
        one layer's weights), so that the program holds ONE layer's code,
        whatever the depth."""
        S = ids.shape[0]
        P = min(_POS_CHUNK, S)
        if S % P:
            raise ValueError(f"prefill length {S} is no multiple of {P}")
        xc = self._embed(params, ids).reshape(S // P, P, self.hidden)
        starts = jnp.arange(S // P) * P
        weights = [functools.partial(lambda b: b, b)
                   for b in params["blocks"]]
        windowed = jnp.asarray(self.windowed)

        def layer(xc, li):
            blk = jax.lax.switch(li, weights)
            xc, kv, c = self._layer_row(blk, windowed[li], xc, starts,
                                        length)
            return xc, (kv, c)

        xc, (kv, counts) = jax.lax.scan(layer, xc, jnp.arange(self.n_layers))
        return xc.reshape(S, self.hidden), kv, counts.sum(0)

    def call(self, params, inputs, *, training=False, rng=None):
        """Teacher-forced logits (B, T, V), a sequence at a time."""
        def row(seq):
            x, _, _ = self._forward_row(params, seq, seq.shape[0])
            return self._logits(params, x)

        return jax.lax.map(row, common.ids(inputs))

    # -- decode: one token a row ---------------------------------------------
    def _attend_parts(self, q, spans, load, need):
        """Softmax attention of one token a row, ``q`` (A, kv heads, group,
        d), over keys in spans: ``load(lo, hi) -> (k, v (A, kv heads, s, d),
        ok (A, s))``; a span after the first runs only where ``need(lo)``.
        Returns (A, heads * d)."""
        A, G, J, d = q.shape
        scale = d ** -0.5
        parts = []
        for i, (lo, hi) in enumerate(spans):
            def chunk(lo=lo, hi=hi):
                k, v, ok = load(lo, hi)
                att = common.ein("agjd,agsd->agjs", q, k, self.dtype) * scale
                att = jnp.where(ok[:, None, None], att, NEG_INF)
                m = att.max(-1)
                e = jnp.exp(att - m[..., None])
                return (m, e.sum(-1),
                        common.ein("agjs,agsd->agjd", e, v, self.dtype))

            def skip():
                return (jnp.full((A, G, J), NEG_INF, jnp.float32),
                        jnp.zeros((A, G, J), jnp.float32),
                        jnp.zeros((A, G, J, d), jnp.float32))

            parts.append(chunk() if i == 0 else jax.lax.cond(need(lo), chunk,
                                                             skip))
        top = functools.reduce(jnp.maximum, [m for m, _, _ in parts])
        den = sum(jnp.exp(m - top) * s for m, s, _ in parts)
        out = sum(jnp.exp(m - top)[..., None] * o for m, _, o in parts)
        return (out / den[..., None]).reshape(A, -1)

    def _window_decode(self, q, rk, rv, pos, active):
        """A window layer: the ring's valid rows (rows r <= pos: all of them
        once the context fills the window), a chunk skipped where no active
        row has reached it."""
        W = rk.shape[2]
        C = min(_DECODE_CHUNK, W)

        def load(lo, hi):
            return rk[:, :, lo:hi], rv[:, :, lo:hi], \
                jnp.arange(lo, hi)[None, :] <= pos[:, None]

        return self._attend_parts(
            q, [(lo, min(lo + C, W)) for lo in range(0, W, C)], load,
            lambda lo: (active & (pos >= lo)).any())

    @staticmethod
    def _full_spans(n: int, bl: int):
        """The XLA path's chunks of a table of ``n`` entries: ``E`` entries
        (``_DECODE_CHUNK`` positions) each."""
        E = max(_DECODE_CHUNK // bl, 1)
        return [(lo, min(lo + E, n)) for lo in range(0, n, E)]

    def _full_decode(self, q, k_pool, v_pool, bt, pos, active, bl):
        """A full layer's XLA path: the context's blocks through the table,
        gathered for every row a chunk of ``_full_spans`` at a time, a chunk
        skipped past every active row's context; an idle slot reads zero, as
        from the kernel."""
        A, G = q.shape[:2]

        def load(lo, hi):
            blocks = jnp.broadcast_to(bt[:, None, lo:hi], (A, G, hi - lo))
            kk = paged.grouped_blocks(k_pool, blocks)
            vv = paged.grouped_blocks(v_pool, blocks)
            tok = lo * bl + jnp.arange((hi - lo) * bl)
            return kk.reshape(A, G, -1, kk.shape[-1]), \
                vv.reshape(A, G, -1, vv.shape[-1]), \
                tok[None, :] <= pos[:, None]

        out = self._attend_parts(
            q, self._full_spans(bt.shape[1], bl), load,
            lambda lo: (active & (pos >= lo * bl)).any())
        return jnp.where(active[:, None], out, 0.0)

    def _full_read(self, pos, active, n: int, bl: int, mode: str):
        """Positions a full layer's read fetches for each row: the kernel
        its live blocks, the XLA path the chunks that ran (every row the
        same)."""
        if mode != "xla":
            return jnp.minimum(-(-(pos + 1) // bl), n) * bl
        return sum(jnp.where((i == 0) | (active & (pos >= lo * bl)).any(),
                             (hi - lo) * bl, 0)
                   for i, (lo, hi) in enumerate(self._full_spans(n, bl)))

    def decode_paged(self, params, state, block_tables, pos, tokens, *,
                     block_len: int, kv_quant: str = "off", impl=None):
        """One token a row (the contract's decode step).  A window layer
        writes its slot's ring and reads it; a full layer appends to its
        pages and reads the live context through the table: ``impl``
        (``ops/dispatch.resolve_impl``) picks the grouped-page kernel
        (``pallas`` on a TPU, ``interpret``) or the chunked XLA gather
        (``xla``, a CPU's), and likewise the expert layer's kernel or its
        ``ragged_dot`` slabs.  An idle slot (table all trash) changes no state
        of its own.  Returns ``(logits, state)``."""
        mode = resolve_impl(impl)
        bl = int(block_len)
        bt = jnp.asarray(block_tables, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        cursor = paged.pool_cursor(bt, pos, bl)
        active = bt[:, 0] != 0
        A, G, J, d = bt.shape[0], self.n_kv, self.group, self.head_dim
        x = self._embed(params, jnp.asarray(tokens, jnp.int32))
        ks, vs = list(state["k"]), list(state["v"])
        rks, rvs = list(state["rk"]), list(state["rv"])
        counts = _no_counts()
        for li, blk in enumerate(params["blocks"]):
            q, k, v = self._qkv(blk, self._rms(blk["ln1"], x), pos,
                                self.windowed[li])
            q = q.reshape(A, G, J, d)
            if self.windowed[li]:
                wi = self.window_ids.index(li)
                with _scope("ring_put"):
                    rks[wi] = paged.ring_put(rks[wi], k, pos, active)
                    rvs[wi] = paged.ring_put(rvs[wi], v, pos, active)
                with _scope("window_attend"):
                    o = self._window_decode(q, rks[wi], rvs[wi], pos, active)
            else:
                fi = self.full_ids.index(li)
                ks[fi], vs[fi] = paged.grouped_append(state, fi, k, v, cursor)
                with _scope("full_attend"):
                    if mode == "xla":
                        o = self._full_decode(q, ks[fi], vs[fi], bt, pos,
                                              active, bl)
                    else:
                        o = paged.grouped_paged_attention(
                            q, ks[fi], vs[fi], bt, pos + 1,
                            interpret=mode == "interpret").reshape(A, -1)
            h = x + common.mm(o, blk["o"])
            y, c = self._experts(blk, h, x, active, decode=True, impl=mode)
            x = h + y
            counts = counts + c
        n_win, n_full = len(self.window_ids), len(self.full_ids)
        read = self._full_read(pos, active, bt.shape[1], bl, mode)
        counts = counts + _count(
            window_keys_attended=jnp.where(
                active, jnp.minimum(pos + 1, self.window), 0).sum() * n_win,
            window_keys_context=jnp.where(active, pos + 1, 0).sum() * n_win,
            full_keys_read=jnp.where(active, read, 0).sum() * n_full,
            full_keys_context=jnp.where(active, pos + 1, 0).sum() * n_full)
        return self._logits(params, x), dict(
            state, k=ks, v=vs, rk=rks, rv=rvs,
            counters=common.bump(state["counters"], counts))

    # -- the paged contract ---------------------------------------------------
    def init_paged_pools(self, n_blocks: int, block_len: int,
                         max_active: int, kv_quant: str = "off"):
        """Zeroed state: ``ops/paged_attention``'s window format at this
        model's depths and widths, and the counters."""
        if kv_quant != "off":
            raise ValueError("the window format has no quantised form")
        return dict(paged.init_window_pools(
            len(self.full_ids), len(self.window_ids), n_blocks, block_len,
            self.n_kv, self.head_dim, self.window, max_active, self.dtype),
            counters=np.zeros((len(COUNTERS), 2), np.int32))

    def paged_state_bytes(self, state):
        out = paged.pool_bytes({k: v for k, v in state.items()
                                if k != "counters"})
        out["lanes"] += int(np.prod(state["counters"].shape)) * 4
        return out

    def paged_counters(self, state):
        return common.read_counters(state["counters"], COUNTERS)

    def prefill_paged(self, params, state, prompt, lengths, dest, slots, *,
                      block_len: int, kv_quant: str = "off"):
        """Rows in sequence inside ONE program (``lax.scan`` carries the
        state), so a batch's temporaries are one row's; a batch's padding
        row (its blocks all trash) is skipped whole.  A row's full layers'
        K/V land in its ``dest`` blocks, its window layers' last ``window``
        positions in its slot's rings."""
        xs = (common.ids(prompt), jnp.asarray(lengths, jnp.int32),
              jnp.asarray(dest, jnp.int32), jnp.asarray(slots, jnp.int32))

        def run(st, ids, n, dst, slot):
            x, (k, v), counts = self._forward_row(params, ids, n)
            ks, vs, _ = paged.grouped_commit(
                st, [k[i] for i in self.full_ids],
                [v[i] for i in self.full_ids], [], dst, block_len=block_len)
            with _scope("ring_put"):
                rk = [paged.ring_commit(r, k[i], n, slot)
                      for r, i in zip(st["rk"], self.window_ids)]
                rv = [paged.ring_commit(r, v[i], n, slot)
                      for r, i in zip(st["rv"], self.window_ids)]
            st = dict(st, k=ks, v=vs, rk=rk, rv=rv,
                      counters=common.bump(st["counters"], counts))
            return st, jnp.take(x, jnp.maximum(n - 1, 0), axis=0)

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
            "WindowMoELM cannot prefill behind a shared prefix: its window "
            "layers' rings are per-slot state that no resident block holds; "
            "serve it with generation.prefix_cache=false")

    # -- contiguous caches: not offered ---------------------------------------
    def init_decode(self, params, prompt, lengths=None,
                    cache_len: Optional[int] = None):
        raise NotImplementedError(
            "WindowMoELM is served through the paged contract only "
            "(generation.paged=true)")

    def decode_step(self, params, state, tokens):
        raise NotImplementedError(
            "WindowMoELM is served through the paged contract only "
            "(generation.paged=true)")
