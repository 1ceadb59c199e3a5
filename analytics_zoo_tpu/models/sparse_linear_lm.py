"""A decoder that mixes two kinds of sequence layer (PR 35): decayed LINEAR
attention, which keeps a fixed-size recurrent state and no keys, and
grouped-query softmax attention that, past a context length, reads only the
best BLOCKS of its cache, chosen from compressed keys.

``SparseLinearLM`` is the block of the MiniCPM-SALA family (``model_type``
``minicpm_sala``: ``mixer_types`` names each layer ``lightning-attn`` or
``minicpm4``), served through the same paged contract as
``models/textmodels.TransformerLM`` (its docstring is the contract's text).
``u`` is the RMS-normed input of a sublayer, ``r = scale_depth /
sqrt(published depth)``:

- model: ``x = E[ids] * scale_emb``; a layer ``h = x + r * Mixer(RMSNorm(x))``,
  ``x' = h + r * SwiGLU(RMSNorm(h))``; ``logits = W_head(RMSNorm(x_L) /
  (hidden_size / dim_model_base))``; no biases;
- ``lightning-attn``: ``q, k, v = u W_q, u W_k, u W_v`` (heads of ``d``); RMS
  norm with a learned gain over the head dimension on ``q`` and ``k``; rotary
  (half-split pairs) on both; a head's state ``S_t = lambda_h S_(t-1) + k_t^T
  v_t`` (float32), ``o_t = (q_t / sqrt(d)) S_t``; RMS norm over the head
  dimension of ``o``; ``o * sigmoid(u W_g)``; ``W_o``.  ``lambda_h = exp(-2 **
  (-8 (h + 1) / heads))``.  Prefill runs the chunked form, decode one step
  (``ops/linear_attention``);
- ``minicpm4`` (InfLLM-V2): ``q`` (heads x d), ``k, v`` (kv heads x d), a GROUP
  of ``heads / kv heads`` query heads a key head, the same norm on ``q`` and
  ``k``, NO rotary.  A query whose context is at most ``dense_len`` attends
  it all.  Beyond it: compressed keys ``kbar_j = mean(k[stride j : stride j +
  kernel])`` of every complete window; per head ``softmax_j(q . kbar_j /
  sqrt(d))`` over the windows that END at or before the query; summed over
  the group; a block's score is the largest among the windows that overlap
  it; block 0 (``init_blocks``) and the ``window_size / block_size`` blocks
  that end with the query's own always count as best; the ``topk`` best blocks
  are kept, the same for the whole group; causal softmax attention over the
  kept blocks' tokens; ``o * sigmoid(u W_g)``; ``W_o``.  Prefill scores dense
  key chunks under the kept blocks' mask (the result is the equations');
  decode gathers the kept blocks, and only them, through the block table.

The state the scheduler carries for the class (one opaque pytree) is
``ops/paged_attention``'s grouped format: ``k`` / ``v`` / ``ck`` pools of the
attention layers (``block_len`` must be the selection's ``block_size``: a kept
block IS a table entry), ``lin`` (every slot's recurrent state, float32) and
``counters``.  Runs of like linear layers are stacked and scanned, so the
programs hold one linear layer's code a run.  Weights are built in ``dtype``
(bfloat16 as served; norms float32), so ``matmul_operands`` is the tree
itself.

Not built: sharing a resident prefix (``prefill_shared_paged`` raises: the
recurrent state of a prefix would have to be snapshotted at block boundaries),
contiguous caches (``init_decode`` / ``decode_step`` raise: the class is served
paged), the published kernels' coarse second-level approximation of the
compressed scores (the softmax over ``kbar`` is exact).
"""

from __future__ import annotations

import itertools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.models import lm_common as common
from analytics_zoo_tpu.models.lm_common import NEG_INF, scope as _scope
from analytics_zoo_tpu.nn.module import Layer
from analytics_zoo_tpu.ops import linear_attention
from analytics_zoo_tpu.ops import paged_attention as paged

_POS_CHUNK = 2048       # prefill positions a layer takes at once (MLP rows)
_QUERY_BLOCK = 256      # ... of which the attention takes this many queries
_KEY_CHUNK = 4096       # ... over this many keys at once
_LIN_CHUNK = 256        # positions one step of the chunked linear form takes

SPARSE, LINEAR = "minicpm4", "lightning-attn"

# What the programs count, in the order of the state's ``counters`` leaf.
# ``sparse_*`` over DECODE rows of the attention layers (idle slots left
# out): a (row, layer) adds 1 to ``sparse_rows``, and to ``sparse_rows_dense``
# when its context is at most ``dense_len``; a (row, layer, key head) adds the
# blocks it read to ``sparse_blocks_kept`` and the blocks its context holds to
# ``sparse_blocks_context``.
COUNTERS = (
    "sparse_blocks_kept",
    "sparse_blocks_context",
    "sparse_rows",
    "sparse_rows_dense",
    "lin_state_updates",          # decode rows x linear layers
    "prefill_positions_linear",   # real prompt positions x linear layers
)


def _no_counts():
    return common.no_counts(COUNTERS)


def _count(**named):
    return common.counts(COUNTERS, **named)


class SparseLinearLM(Layer):
    """See the module docstring.  Constructor arguments carry the names of
    the published ``config.json``; ``from_config`` reads one."""

    # the scheduler refuses ``prefix_cache`` over this class at start
    paged_prefix_sharing = False

    def __init__(self, vocab_size: int, hidden_size: int,
                 num_hidden_layers: int, mixer_types: Sequence[str],
                 intermediate_size: int, num_attention_heads: int,
                 num_key_value_heads: int, head_dim: int, lightning_nh: int,
                 lightning_nkv: int, lightning_head_dim: int,
                 sparse_config: dict, scale_emb: float = 1.0,
                 scale_depth: float = 1.0,
                 dim_model_base: Optional[int] = None,
                 mup_layers: Optional[int] = None,
                 rope_theta: float = 1e4, rms_norm_eps: float = 1e-6,
                 max_position_embeddings: int = 32768,
                 dtype: str = "bfloat16", initializer_range: float = 0.02,
                 **kwargs):
        super().__init__(**kwargs)
        self.vocab_size = int(vocab_size)
        self.hidden = int(hidden_size)
        self.n_layers = int(num_hidden_layers)
        self.kinds = tuple(mixer_types)
        if len(self.kinds) != self.n_layers \
                or set(self.kinds) - {SPARSE, LINEAR}:
            raise ValueError(f"mixer_types must name {self.n_layers} layers "
                             f"{SPARSE!r} or {LINEAR!r}, got {self.kinds}")
        # runs of like layers: an attention layer stands alone (its pools are
        # leaves of their own), linear layers are stacked a run
        self.runs = []
        for kind, group in itertools.groupby(self.kinds):
            n = len(list(group))
            self.runs += [(LINEAR, n)] if kind == LINEAR else [(SPARSE, 1)] * n
        self.n_sparse = self.kinds.count(SPARSE)
        self.n_linear = self.kinds.count(LINEAR)
        self.width = int(intermediate_size)
        self.n_head, self.n_kv = int(num_attention_heads), \
            int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.lin_heads, self.lin_dim = int(lightning_nh), \
            int(lightning_head_dim)
        if int(lightning_nkv) != self.lin_heads:
            raise ValueError("lightning_nkv must equal lightning_nh")
        if self.n_head % self.n_kv:
            raise ValueError(f"{self.n_head} query heads over {self.n_kv} "
                             f"key heads")
        self.group = self.n_head // self.n_kv
        self.scale_emb = float(scale_emb)
        self.r = float(scale_depth) / np.sqrt(mup_layers or self.n_layers)
        self.logit_div = self.hidden / float(dim_model_base or self.hidden)
        sc = sparse_config      # the source gives none: the file states it
        self.kernel, self.stride = int(sc["kernel_size"]), \
            int(sc["kernel_stride"])
        self.block, self.topk = int(sc["block_size"]), int(sc["topk"])
        self.init_blocks = int(sc["init_blocks"])
        self.local_blocks = int(sc["window_size"]) // self.block
        self.dense_len = int(sc["dense_len"])
        if self.kernel != 2 * self.stride or self.block % self.stride \
                or self.dense_len < self.kernel \
                or self.init_blocks + self.local_blocks > self.topk:
            raise ValueError(f"sparse_config {sc}: kernel_size must be twice "
                             f"kernel_stride, block_size a multiple of it, "
                             f"dense_len >= kernel_size, and the forced "
                             f"blocks must fit in topk")
        self.windows = self.block // self.stride     # that BEGIN in a block
        self.theta, self.eps = float(rope_theta), float(rms_norm_eps)
        self.max_len = int(max_position_embeddings)
        self.dtype = jnp.dtype(dtype)
        self.std = float(initializer_range)
        h = np.arange(1, self.lin_heads + 1, dtype=np.float64)
        self.decay = np.exp(-2.0 ** (-8.0 * h / self.lin_heads)).astype(
            np.float32)
        self._declared_input_shape = (None,)

    @classmethod
    def from_config(cls, cfg: dict, **overrides) -> "SparseLinearLM":
        """From a published ``config.json`` as a configuration file cuts it:
        ``num_hidden_layers`` / ``mixer_types`` are the layers held here,
        ``published.num_hidden_layers`` the depth the residual scale keeps.
        Keys the class does not know are not read."""
        import inspect
        known = set(inspect.signature(cls.__init__).parameters) - {"self"}
        kw = {k: v for k, v in cfg.items() if k in known}
        kw.setdefault("mup_layers", (cfg.get("published") or {}).get(
            "num_hidden_layers", cfg["num_hidden_layers"]))
        kw.update(overrides)
        return cls(**kw)

    # -- weights --------------------------------------------------------------
    def build(self, rng, input_shape=None):
        """Random weights from ``rng`` (normal, ``initializer_range``), in
        ``dtype``; norm gains 1 + 0.1 n in float32.  ``runs[i]`` holds run
        i's layers: an attention layer's weights as they are, a linear run's
        stacked on a leading axis."""
        H, F, dt, std = self.hidden, self.width, self.dtype, self.std
        keys = iter(jax.random.split(rng, 4 + 16 * len(self.runs)))

        def w(*shape, dtype=dt, scale=std):
            return (scale * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dtype)

        def gain(*shape):
            return 1.0 + w(*shape, dtype=jnp.float32, scale=0.1)

        def layer(lead, nq, nkv, d, extra):
            blk = {"ln1": gain(*lead, H), "ln2": gain(*lead, H),
                   "q": w(*lead, H, nq * d), "k": w(*lead, H, nkv * d),
                   "v": w(*lead, H, nkv * d), "q_ln": gain(*lead, d),
                   "k_ln": gain(*lead, d), "g": w(*lead, H, nq * d),
                   "o": w(*lead, nq * d, H), "gate": w(*lead, H, F),
                   "up": w(*lead, H, F), "down": w(*lead, F, H)}
            blk.update({name: gain(*lead, d) for name in extra})
            return blk

        runs = [layer((), self.n_head, self.n_kv, self.head_dim, ())
                if kind == SPARSE else
                layer((n,), self.lin_heads, self.lin_heads, self.lin_dim,
                      ("o_ln",)) for kind, n in self.runs]
        return {"embed": w(self.vocab_size, H), "ln_f": gain(H),
                "head": w(H, self.vocab_size), "runs": runs}

    def matmul_operands(self, params, dtype):
        """The tree is built in its operand type: nothing to round."""
        return params

    # -- shared pieces --------------------------------------------------------
    def _rms(self, g, x):
        return common.rms(g, x, self.eps)

    def _mlp(self, blk, x):
        return x + self.r * common.swiglu(
            self._rms(blk["ln2"], x), blk["gate"], blk["up"], blk["down"])

    def _logits(self, params, h):
        return common.mm(self._rms(params["ln_f"], h) / self.logit_div,
                         params["head"])

    def _embed(self, params, ids):
        return jnp.take(params["embed"], ids, axis=0).astype(jnp.float32) \
            * self.scale_emb

    def _lin_qkv(self, blk, u, pos, barrier: bool = False):
        """A linear layer's normed, turned, scaled queries, keys and values
        of tokens ``u`` (T, H) at ``pos`` (T,): (T, heads, d) each.
        ``barrier`` (the decode step) keeps the projections' outputs as the
        matmuls lay them out: without it the compiler transposes the three
        stacked weights of every linear layer, a call, to get the step's
        outer product its keys in columns."""
        T, nh, d = u.shape[0], self.lin_heads, self.lin_dim
        q, k, v = (common.mm(u, blk[name]) for name in "qkv")
        if barrier:
            q, k, v = jax.lax.optimization_barrier((q, k, v))
        q = common.rotary_half(self._rms(blk["q_ln"], q.reshape(T, nh, d)),
                               pos, self.theta)
        k = common.rotary_half(self._rms(blk["k_ln"], k.reshape(T, nh, d)),
                               pos, self.theta)
        return q * d ** -0.5, k, v.reshape(T, nh, d)

    def _lin_out(self, blk, x, u, o):
        """The rest of a linear layer after its attention output ``o`` (T,
        heads, d): output norm, gate, projection, residual, feed-forward."""
        o = self._rms(blk["o_ln"], o).reshape(o.shape[0], -1) \
            * jax.nn.sigmoid(common.mm(u, blk["g"]))
        return self._mlp(blk, x + self.r * common.mm(o, blk["o"]))

    def _sparse_qkv(self, blk, u):
        T = u.shape[0]
        q = self._rms(blk["q_ln"], common.mm(u, blk["q"]).reshape(
            T, self.n_head, self.head_dim))
        k = self._rms(blk["k_ln"], common.mm(u, blk["k"]).reshape(
            T, self.n_kv, self.head_dim))
        return q, k, common.mm(u, blk["v"]).reshape(T, self.n_kv,
                                                    self.head_dim)

    def _sparse_out(self, blk, x, u, o):
        o = o * jax.nn.sigmoid(common.mm(u, blk["g"]))
        return self._mlp(blk, x + self.r * common.mm(o, blk["o"]))

    def _block_scores(self, p):
        """Compressed-key probabilities ``p`` (..., windows) summed over a
        group -> a score a block (..., blocks): the largest among the
        windows that overlap the block, which are the ``windows`` that begin
        in it and the one before them (``kernel_size`` is two strides)."""
        pw = p.reshape(p.shape[:-1] + (-1, self.windows))
        before = jnp.concatenate(
            [jnp.zeros_like(pw[..., :1, -1]), pw[..., :-1, -1]], axis=-1)
        return jnp.maximum(pw.max(-1), before)

    def _forced(self, n_blocks, qb):
        """``(forced, ok)`` (..., n_blocks) for queries in block ``qb``
        (...): the blocks that always count as best, and the causal ones."""
        b = jnp.arange(n_blocks)
        qb = qb[..., None]
        return ((b < self.init_blocks) | (b > qb - self.local_blocks)) \
            & (b <= qb), b <= qb

    # -- prefill: one sequence, a layer and a chunk of positions at a time ----
    def _linear_run_row(self, run, xc, starts, length):
        """A run of stacked linear layers over one sequence ``xc`` (chunks,
        P, H).  Returns ``(xc, states (n, heads, d, d))``."""
        decay = jnp.asarray(self.decay)
        P = xc.shape[1]

        def layer(xc, blk):
            def live(S, x, start):
                u = self._rms(blk["ln1"], x)
                with _scope("lin_chunk"):
                    q, k, v = self._lin_qkv(blk, u, start + jnp.arange(P))
                    o, S = linear_attention.chunked(
                        q[None], k[None], v[None], decay, S[None],
                        (length - start)[None], chunk=_LIN_CHUNK,
                        dtype=self.dtype)
                return S[0], self._lin_out(blk, x, u, o[0])

            def chunk(S, inp):
                # a chunk of padding alone is not computed
                return jax.lax.cond(inp[1] < length, live,
                                    lambda S, x, start: (S, x), S, *inp)

            S0 = jnp.zeros((self.lin_heads, self.lin_dim, self.lin_dim),
                           jnp.float32)
            S, xc = jax.lax.scan(chunk, S0, (xc, starts))
            return xc, S

        return jax.lax.scan(layer, xc, run)

    def _sparse_row(self, blk, xc, starts, length):
        """An attention layer over one sequence ``xc`` (chunks, P, H).
        Returns ``(xc, (k, v, ck))``: the sequence's cache rows (S, kv heads,
        d) and compressed keys (S // stride, kv heads, d; window j at row j,
        the last row an incomplete window's), in the cache's type."""
        NC, P, _ = xc.shape
        S, G, J, d, dt = NC * P, self.n_kv, self.group, self.head_dim, \
            self.dtype
        qblk = min(_QUERY_BLOCK, P)
        if P % qblk or S % self.block:
            raise ValueError(f"prefill length {S} in chunks of {P}: no "
                             f"multiple of {qblk} / {self.block}")
        scale = d ** -0.5

        def kv(_, inp):
            def live(x):
                _, k, v = self._sparse_qkv(blk, self._rms(blk["ln1"], x))
                return k.astype(dt), v.astype(dt)

            # (a chunk of padding alone is not computed: no real query
            # attends its keys)
            return None, jax.lax.cond(
                inp[1] < length, live,
                lambda x: (jnp.zeros((P, G, d), dt),) * 2, inp[0])

        _, (k, v) = jax.lax.scan(kv, None, (xc, starts))
        k, v = k.reshape(S, G, d), v.reshape(S, G, d)
        with _scope("sparse_compress"):
            # window j = strides j and j + 1, from the keys as cached
            part = k.astype(jnp.float32).reshape(
                S // self.stride, self.stride, G, d).sum(1)
            ck = jnp.concatenate(
                [(part[:-1] + part[1:]) / self.kernel,
                 jnp.zeros_like(part[:1])]).astype(dt)
        key_pos = jnp.arange(S)
        w_end = jnp.arange(S // self.stride) * self.stride + self.kernel - 1

        def attend(q, t):
            q = q.reshape(qblk, G, J, d)             # (qblk, heads, d), (qblk,)
            with _scope("sparse_score"):
                seen = w_end[None, :] <= t[:, None]             # (q, windows)
                sc = common.ein("qgjd,wgd->gjqw", q, ck, dt) * scale
                p = jax.nn.softmax(jnp.where(seen, sc, NEG_INF), axis=-1)
                score = self._block_scores(jnp.where(seen, p, 0.0).sum(1))
            with _scope("sparse_select"):
                forced, ok = self._forced(S // self.block, t // self.block)
                dense = (t + 1 <= self.dense_len)[:, None]
                keep = common.topk_mask(
                    jnp.where(forced, jnp.inf, score).reshape(G * qblk, -1),
                    jnp.broadcast_to(ok, (G,) + ok.shape).reshape(
                        G * qblk, -1), self.topk).reshape(G, qblk, -1)
                keep = jnp.where(dense, ok, keep)               # (G, q, blocks)

            def allowed(lo, hi):
                # keys lo .. hi: the kept blocks' tokens, causal; a query's
                # J heads are J rows of the folded query axis
                m = jnp.repeat(keep[:, :, lo // self.block:hi // self.block],
                               self.block, axis=-1) \
                    & (key_pos[None, None, lo:hi] <= t[None, :, None])
                return jnp.repeat(m, J, axis=1)

            with _scope("sparse_attend"):
                o = common.attend_chunks(
                    q.transpose(0, 2, 1, 3).reshape(qblk * J, G, d), k, v,
                    allowed, key_pos, t[-1], scale, dt,
                    max(_KEY_CHUNK // self.block, 1) * self.block)
            return o.reshape(qblk, J, G, d).transpose(0, 2, 1, 3).reshape(
                qblk, G * J * d)

        def block(args):
            return jax.lax.cond(
                args[1][0] < length, attend,
                lambda q, t: jnp.zeros((qblk, G * J * d), jnp.float32), *args)

        def live(x, start):
            u = self._rms(blk["ln1"], x)
            q, _, _ = self._sparse_qkv(blk, u)
            o = jax.lax.map(block, (
                q.reshape((P // qblk, qblk) + q.shape[1:]),
                (start + jnp.arange(P)).reshape(P // qblk, qblk)))
            return self._sparse_out(blk, x, u, o.reshape(P, -1))

        def chunk(_, inp):
            return None, jax.lax.cond(inp[1] < length, live,
                                      lambda x, start: x, *inp)

        _, xc = jax.lax.scan(chunk, None, (xc, starts))
        return xc, (k, v, ck)

    def _forward_row(self, params, ids, length):
        """One sequence through the stack: ``ids`` (S,) right-padded tokens
        of which ``length`` are real.  Returns ``(h (S, H), caches, states)``:
        the last layer's output, each attention layer's ``(k, v, ck)`` and the
        linear layers' states (n_linear, heads, d, d) after the last real
        position."""
        S = ids.shape[0]
        P = min(_POS_CHUNK, S)
        if S % P:
            raise ValueError(f"prefill length {S} is no multiple of {P}")
        xc = self._embed(params, ids).reshape(S // P, P, self.hidden)
        starts = jnp.arange(S // P) * P
        caches, states = [], []
        for (kind, _), run in zip(self.runs, params["runs"]):
            if kind == LINEAR:
                xc, st = self._linear_run_row(run, xc, starts, length)
                states.append(st)
            else:
                xc, kept = self._sparse_row(run, xc, starts, length)
                caches.append(kept)
        states = jnp.concatenate(states) if states else jnp.zeros(
            (0, self.lin_heads, self.lin_dim, self.lin_dim), jnp.float32)
        return xc.reshape(S, self.hidden), caches, states

    def call(self, params, inputs, *, training=False, rng=None):
        """Teacher-forced logits (B, T, V), a sequence at a time."""
        def row(seq):
            h, _, _ = self._forward_row(params, seq, seq.shape[0])
            return self._logits(params, h)

        return jax.lax.map(row, common.ids(inputs))

    # -- decode: one token a row ---------------------------------------------
    def _sparse_decode(self, blk, state, li, x, bt, pos, cursor, active):
        """Attention layer ``li`` (among the attention layers) over one token
        a row.  Returns ``(x, (k, v, ck) leaves, counts)``."""
        A, G, J, d, bl = x.shape[0], self.n_kv, self.group, self.head_dim, \
            self.block
        NB, scale = bt.shape[1], d ** -0.5
        u = self._rms(blk["ln1"], x)
        q, k, v = self._sparse_qkv(blk, u)
        q = q.reshape(A, G, J, d)
        with _scope("sparse_compress"):
            k_pool, v_pool = paged.grouped_append(state, li, k, v, cursor)
            # the token may complete the window that ends with it
            done = ((pos + 1) % self.stride == 0) & (pos + 1 >= self.kernel) \
                & active
            span = jnp.maximum(
                pos[:, None] - self.kernel + 1 + jnp.arange(self.kernel), 0)
            ck_row = paged.grouped_rows(k_pool, bt, span, bl).astype(
                jnp.float32).sum(1) / self.kernel
            ck_pool = paged.compressed_put(
                state["ck"][li], bt, (pos + 1 - self.kernel) // self.stride,
                ck_row, done, self.windows)
        with _scope("sparse_score"):
            cks = paged.compressed_gather(ck_pool, bt, G, d)
            w_end = jnp.arange(cks.shape[1]) * self.stride + self.kernel - 1
            seen = (w_end[None, :] <= pos[:, None])[:, None, None]
            sc = common.ein("agjd,awgd->agjw", q, cks, self.dtype) * scale
            p = jax.nn.softmax(jnp.where(seen, sc, NEG_INF), axis=-1)
            score = self._block_scores(jnp.where(seen, p, 0.0).sum(2))
        with _scope("sparse_select"):
            qb = pos // bl
            forced, ok = self._forced(NB, qb)                   # (A, NB)
            dense = pos + 1 <= self.dense_len
            score = jnp.where((forced | dense[:, None])[:, None], jnp.inf,
                              score)
            score = jnp.where(ok[:, None], score, -jnp.inf)     # (A, G, NB)

        def attend(n_keep):
            with _scope("sparse_select"):
                _, idx = jax.lax.top_k(score, n_keep)           # (A, G, n)
                limit = jnp.where(dense, n_keep, self.topk)[:, None, None]
                sel_ok = (idx <= qb[:, None, None]) \
                    & (jnp.arange(n_keep) < limit)
                blocks = jnp.take_along_axis(
                    jnp.broadcast_to(bt[:, None], (A, G, NB)), idx, axis=2)
            with _scope("sparse_attend"):
                kk = paged.grouped_blocks(k_pool, blocks).reshape(A, G, -1, d)
                vv = paged.grouped_blocks(v_pool, blocks).reshape(A, G, -1, d)
                tok = (idx[..., None] * bl + jnp.arange(bl)).reshape(A, G, -1)
                allowed = jnp.repeat(sel_ok, bl, axis=-1) \
                    & (tok <= pos[:, None, None])
                att = common.ein("agjd,agsd->agjs", q, kk, self.dtype) * scale
                att = jax.nn.softmax(
                    jnp.where(allowed[:, :, None], att, NEG_INF), axis=-1)
                o = common.ein("agjs,agsd->agjd", att, vv, self.dtype)
            return o.reshape(A, -1), \
                (sel_ok & active[:, None, None]).sum().astype(jnp.int32)

        n_sparse = min(self.topk, NB)
        n_dense = min(max(self.dense_len // bl, self.topk), NB)
        if n_dense > n_sparse:
            # a row whose context is still dense reads every block of it
            o, kept = jax.lax.cond((dense & active).any(),
                                   lambda: attend(n_dense),
                                   lambda: attend(n_sparse))
        else:
            o, kept = attend(n_sparse)
        counts = _count(
            sparse_blocks_kept=kept,
            sparse_blocks_context=(jnp.where(active, qb + 1, 0) * G).sum(),
            sparse_rows=active.sum(),
            sparse_rows_dense=(dense & active).sum())
        return self._sparse_out(blk, x, u, o), (k_pool, v_pool, ck_pool), \
            counts

    def _linear_run_decode(self, run, x, lin, first, pos, active):
        """A run of stacked linear layers (the state's layers ``first ...``)
        over one token a row.  Returns ``(x, lin)``."""
        decay = jnp.asarray(self.decay)

        # unrolled over the run's layers, each a static slice of the stacked
        # weights and of the state: scanned, a step copies every layer's
        # weights out of the stack before it multiplies by them
        for i in range(jax.tree.leaves(run)[0].shape[0]):
            blk = jax.tree.map(lambda a: a[i], run)
            u = self._rms(blk["ln1"], x)
            with _scope("lin_step"):
                q, k, v = self._lin_qkv(blk, u, pos, barrier=True)
                S = lin[first + i]
                o, S2 = linear_attention.step(q, k, v, decay, S)
                # an idle slot's state stays as it is
                lin = lin.at[first + i].set(
                    jnp.where(active[:, None, None, None], S2, S))
            x = self._lin_out(blk, x, u, o)
        return x, lin

    def decode_paged(self, params, state, block_tables, pos, tokens, *,
                     block_len: int, kv_quant: str = "off", impl=None):
        """One token a row (the contract's decode step; the kept blocks come
        through the block table by an XLA gather, so ``impl`` has nothing to
        choose).  A linear layer reads and writes its slot's state, an
        attention layer appends, completes a compressed key when a window
        fills, scores, keeps and gathers.  An idle slot (table all trash)
        changes no state of its own.  Returns ``(logits, state)``."""
        self._check_block(block_len)
        bt = jnp.asarray(block_tables, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        cursor = paged.pool_cursor(bt, pos, self.block)
        active = bt[:, 0] != 0
        x = self._embed(params, jnp.asarray(tokens, jnp.int32))
        ks, vs, cks = list(state["k"]), list(state["v"]), list(state["ck"])
        lin, counts, si, li = state["lin"], _no_counts(), 0, 0
        for (kind, n), run in zip(self.runs, params["runs"]):
            if kind == LINEAR:
                x, lin = self._linear_run_decode(run, x, lin, li, pos, active)
                li += n
            else:
                x, (ks[si], vs[si], cks[si]), c = self._sparse_decode(
                    run, state, si, x, bt, pos, cursor, active)
                counts = counts + c
                si += 1
        counts = counts + _count(
            lin_state_updates=active.sum() * self.n_linear)
        return self._logits(params, x), dict(
            state, k=ks, v=vs, ck=cks, lin=lin,
            counters=common.bump(state["counters"], counts))

    # -- the paged contract ---------------------------------------------------
    def _check_block(self, block_len):
        if int(block_len) != self.block:
            raise ValueError(
                f"block_len={block_len}: this model's pool block is its "
                f"selection's block_size={self.block}")

    def init_paged_pools(self, n_blocks: int, block_len: int,
                         max_active: int, kv_quant: str = "off"):
        """Zeroed state: ``ops/paged_attention``'s grouped format at this
        model's depths and widths, and the counters."""
        if kv_quant != "off":
            raise ValueError("the grouped pool has no quantised format")
        self._check_block(block_len)
        return dict(paged.init_grouped_pools(
            self.n_sparse, n_blocks, block_len, self.n_kv, self.head_dim,
            self.windows, self.n_linear, max_active, self.lin_heads,
            self.lin_dim, self.dtype),
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
        rows (slot = the drop sentinel) are skipped whole.  A row's cache
        rows land in its ``dest`` blocks, its recurrent states at its slot."""
        self._check_block(block_len)
        n_slots = state["lin"].shape[1]
        xs = (common.ids(prompt), jnp.asarray(lengths, jnp.int32),
              jnp.asarray(dest, jnp.int32), jnp.asarray(slots, jnp.int32))

        def run(st, ids, n, dst, slot):
            h, caches, states = self._forward_row(params, ids, n)
            ks, vs, cks = paged.grouped_commit(
                st, *map(list, zip(*caches)), dst, block_len=self.block) \
                if caches else ([], [], [])
            counts = _count(prefill_positions_linear=n * self.n_linear)
            st = dict(st, k=ks, v=vs, ck=cks,
                      lin=paged.grouped_state_put(st["lin"], states, slot),
                      counters=common.bump(st["counters"], counts))
            return st, jnp.take(h, jnp.maximum(n - 1, 0), axis=0)

        def skip(st, ids, n, dst, slot):
            return st, jnp.zeros((self.hidden,), jnp.float32)

        def row(st, x):
            return jax.lax.cond(x[3] < n_slots, run, skip, st, *x)

        # the head once a call, outside the rows' loop
        state, last = jax.lax.scan(row, state, xs)
        return state, self._logits(params, last)

    def prefill_shared_paged(self, params, state, suffix, lengths,
                             prefix_len, ptab, dest, slots, *,
                             block_len: int, kv_quant: str = "off"):
        raise NotImplementedError(
            "SparseLinearLM cannot prefill behind a shared prefix: the "
            "linear layers' recurrent state at the prefix's end is not kept "
            "(it would have to be snapshotted at block boundaries); serve it "
            "with generation.prefix_cache=false")

    # -- contiguous caches: not offered ---------------------------------------
    def init_decode(self, params, prompt, lengths=None,
                    cache_len: Optional[int] = None):
        raise NotImplementedError(
            "SparseLinearLM is served through the paged contract only "
            "(generation.paged=true)")

    def decode_step(self, params, state, tokens):
        raise NotImplementedError(
            "SparseLinearLM is served through the paged contract only "
            "(generation.paged=true)")
