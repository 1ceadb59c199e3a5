"""A decoder with latent attention, a learned sparse selection over it and a
routed expert layer that holds its chip's share of the experts (PR 32).

``LatentMoELM`` is the block of the DeepSeek-V3.2 / GLM-5 family
(``model_type`` ``glm_moe_dsa``), served through the same paged contract as
``models/textmodels.TransformerLM`` (its docstring is the contract's text):

- pre-norm residual blocks with RMS norm, no biases, SwiGLU feed-forward;
- **MLA**: queries through a low-rank bottleneck (``q_a`` -> RMS norm ->
  ``q_b``), keys and values through ONE latent row a token (``kv_a`` -> ``c_kv``
  | ``k_r``), rotary positions (interleaved pairs) on ``qk_rope_head_dim`` dims
  of the query and on ``k_r``, which all heads share.  Prefill EXPANDS the
  latent rows to per-head keys and values (``kv_b``); decode ABSORBS ``kv_b``
  into the query and the output, so the cache holds ``c_kv`` and ``k_r`` only;
- **DSA**: an indexer (``index_n_heads`` x ``index_head_dim``) scores every
  earlier token and attention runs over the ``index_topk`` best only.  Prefill
  masks the dense scores (an exact k-th-largest threshold a query), a block of
  ``_QUERY_BLOCK`` queries at a time, and does not run a block that lies past
  the row's length (PR 36): a call's time follows the document, not the
  bucket; decode gathers the selected rows through the block table;
- **routed FFN**: sigmoid scores over ALL ``n_routed_experts``, the
  ``num_experts_per_tok`` largest of score + selection bias, normalised and
  scaled; this process computes the pairs that land on the experts it HOLDS
  (``experts_held = (first, count)``: a chip's share of an expert-parallel
  deployment), no pair dropped at any imbalance (pairs sorted by expert, slabs
  of them through ``jax.lax.ragged_dot`` until none is left), plus the shared
  expert; what the absent experts would add is left out.  The routed part
  is ``lm_common.routed_experts``, which ``WindowMoELM`` calls too.

The decoder block is written once (``_blocks``); the paths supply their
attention step.  The pool's device format (one ``[c_kv | k_r]`` row and one
indexer key a token a layer) belongs to ``ops/paged_attention``.  Weights are
built in ``dtype`` (bfloat16 as served; the router and the norms float32), so
``matmul_operands`` is the tree itself.

Counters (the contract's optional ``paged_counters``): the programs add to a
small int32 leaf of the state; see ``COUNTERS``.  Its last two say how often
the prefill's skip engages: the query blocks a prefill program was asked for
(bucket / ``_QUERY_BLOCK`` a row a layer) and those that held a real query
and so ran.

Not built: the multi-token-prediction layer (the base model's logits do not
depend on it), the indexer's FP8 / Hadamard rotation (quantisation aids).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.models import lm_common as common
from analytics_zoo_tpu.models.lm_common import NEG_INF, scope as _scope
from analytics_zoo_tpu.nn.module import Layer
from analytics_zoo_tpu.ops import paged_attention as paged

_QUERY_BLOCK = 256      # prefill queries attended at once ...
_KEY_CHUNK = 4096       # ... over this many keys at once: bounds (heads, q, keys)
_PAIR_SLAB = 2048       # token-expert pairs one grouped matmul takes

# What the programs count, in the order of the state's ``counters`` leaf.
# ``moe_*`` over every expert-layer call of every program, real tokens only
# (no padding, no idle slot), except the two marked (decode): a prefill call
# touches every expert, so they describe the decode step alone.  ``dsa_*``
# over decode rows only (a prefill's selection is a mask, not a gather).
# ``prefill_query_blocks*`` over every row a prefill program is given, a
# batch's padding row included (it runs the blocks of the length it is given).
COUNTERS = (
    "moe_pairs",            # token-expert pairs routed, over all experts
    "moe_pairs_held",       # ... that landed on an expert held here
    "moe_pairs_busiest",    # ... on the busiest held expert, summed a call
    "moe_experts_touched",  # (decode) held experts with >= 1 pair, summed
    "moe_layer_steps",      # (decode) expert-layer calls
    "dsa_keys_selected",    # keys attention read, summed a row a layer
    "dsa_keys_context",     # keys in context there (selected <= context)
    "prefill_query_blocks",       # blocks of a row's bucket, summed a layer
    "prefill_query_blocks_live",  # ... that held a real query, and so ran
)


def _no_counts():
    return common.no_counts(COUNTERS)


class LatentMoELM(Layer):
    """See the module docstring.  Constructor arguments carry the names of
    the published ``config.json``; ``from_config`` reads one."""

    def __init__(self, vocab_size: int, hidden_size: int,
                 num_hidden_layers: int, first_k_dense_replace: int,
                 intermediate_size: int, moe_intermediate_size: int,
                 n_routed_experts: int, num_experts_per_tok: int,
                 num_attention_heads: int, q_lora_rank: int,
                 kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int, index_n_heads: int,
                 index_head_dim: int, index_topk: int,
                 n_shared_experts: int = 1,
                 routed_scaling_factor: float = 2.5,
                 rope_theta: float = 1e6, rms_norm_eps: float = 1e-5,
                 max_position_embeddings: int = 8192,
                 experts_held: Optional[Tuple[int, int]] = None,
                 dtype: str = "bfloat16", initializer_range: float = 0.02,
                 **kwargs):
        super().__init__(**kwargs)
        self.vocab_size = int(vocab_size)
        self.hidden = int(hidden_size)
        self.n_layers = int(num_hidden_layers)
        self.n_dense = int(first_k_dense_replace)
        self.dense_width = int(intermediate_size)
        self.expert_width = int(moe_intermediate_size)
        self.n_experts = int(n_routed_experts)
        self.top_k = int(num_experts_per_tok)
        self.n_shared = int(n_shared_experts)
        self.route_scale = float(routed_scaling_factor)
        self.n_head = int(num_attention_heads)
        self.q_rank, self.kv_rank = int(q_lora_rank), int(kv_lora_rank)
        self.nope, self.rope = int(qk_nope_head_dim), int(qk_rope_head_dim)
        self.v_dim = int(v_head_dim)
        self.index_heads = int(index_n_heads)
        self.index_dim = int(index_head_dim)
        self.index_topk = int(index_topk)
        self.theta, self.eps = float(rope_theta), float(rms_norm_eps)
        self.max_len = int(max_position_embeddings)
        first, count = experts_held or (0, self.n_experts)
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held={experts_held} outside "
                             f"{self.n_experts} experts")
        if self.rope % 2 or self.rope > self.index_dim:
            raise ValueError(f"qk_rope_head_dim={self.rope} must be even "
                             f"and <= index_head_dim={self.index_dim}")
        self.experts_held = (int(first), int(count))
        self.dtype = jnp.dtype(dtype)
        self.std = float(initializer_range)
        self.kv_width = paged.latent_width(self.kv_rank, self.rope)
        self._declared_input_shape = (None,)

    @classmethod
    def from_config(cls, cfg: dict, **overrides) -> "LatentMoELM":
        """From a published ``config.json`` as a configuration file cuts it
        (the sizing guide's convention): ``n_routed_experts`` counts the
        experts HELD here when ``published.n_routed_experts`` gives the
        router's width, ``deployment.chip`` says which share (chip c holds
        experts ``c * held ...``).  Keys the class does not know are not
        read."""
        import inspect
        known = set(inspect.signature(cls.__init__).parameters) - {"self"}
        kw = {k: v for k, v in cfg.items() if k in known}
        kw.setdefault("rope_theta",
                      (cfg.get("rope_parameters") or {}).get("rope_theta",
                                                             1e6))
        held = int(cfg["n_routed_experts"])
        total = int((cfg.get("published") or {}).get("n_routed_experts",
                                                     held))
        chip = int((cfg.get("deployment") or {}).get("chip", 0))
        kw.update(n_routed_experts=total, experts_held=(chip * held, held))
        kw.update(overrides)
        return cls(**kw)

    # -- weights --------------------------------------------------------------
    def build(self, rng, input_shape=None):
        """Random weights from ``rng`` (normal, ``initializer_range``), in
        ``dtype``; norm gains 1 + 0.1 n and the selection bias 0.02 n so that
        neither is a no-op; the router and every norm in float32."""
        H, dt, std = self.hidden, self.dtype, self.std
        nh, ih, idim = self.n_head, self.index_heads, self.index_dim
        keys = iter(jax.random.split(rng, 4 + 32 * self.n_layers))

        def w(*shape, dtype=dt, scale=std):
            return (scale * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dtype)

        def gain(n):
            return 1.0 + w(n, dtype=jnp.float32, scale=0.1)

        def ffn(prefix, lead, width):
            return {prefix + "gate": w(*lead, H, width),
                    prefix + "up": w(*lead, H, width),
                    prefix + "down": w(*lead, width, H)}

        blocks = []
        for li in range(self.n_layers):
            blk = {
                "ln1": gain(H), "ln2": gain(H),
                "q_a": w(H, self.q_rank), "q_a_ln": gain(self.q_rank),
                "q_b": w(self.q_rank, nh * (self.nope + self.rope)),
                "kv_a": w(H, self.kv_rank + self.rope),
                "kv_a_ln": gain(self.kv_rank),
                "kv_b": w(self.kv_rank, nh * (self.nope + self.v_dim)),
                "o": w(nh * self.v_dim, H),
                "wq_b": w(self.q_rank, ih * idim), "wk": w(H, idim),
                "k_ln": {"g": gain(idim),
                         "b": w(idim, dtype=jnp.float32, scale=0.1)},
                "w_proj": w(H, ih)}
            if li < self.n_dense:
                blk.update(ffn("", (), self.dense_width))
            else:
                blk["router"] = w(H, self.n_experts, dtype=jnp.float32)
                blk["e_bias"] = w(self.n_experts, dtype=jnp.float32)
                blk.update(ffn("w_", (self.experts_held[1],),
                               self.expert_width))
                blk.update(ffn("s_", (), self.n_shared * self.expert_width))
            blocks.append(blk)
        return {"embed": w(self.vocab_size, H), "ln_f": gain(H),
                "head": w(H, self.vocab_size), "blocks": blocks}

    def matmul_operands(self, params, dtype):
        """The tree is built in its operand type: nothing to round."""
        return params

    # -- shared pieces (``models/lm_common``, at this model's settings) --------
    _mm = staticmethod(common.mm)
    _swiglu = staticmethod(common.swiglu)
    _ids = staticmethod(common.ids)
    _topk_mask = staticmethod(common.topk_mask)
    _bump = staticmethod(common.bump)

    def _ein(self, spec, a, b):
        return common.ein(spec, a, b, self.dtype)

    def _rms(self, g, x):
        return common.rms(g, x, self.eps)

    def _rotary(self, x, pos):
        return common.rotary(x, pos, self.theta)

    def _moe(self, blk, h, valid, decode: bool, impl=None):
        """The routed layer over tokens ``h`` (T, H), of which ``valid``
        (T,) are real (``lm_common.routed_experts``, sigmoid scoring; the
        router reads ``h``; ``impl`` as it takes it).  Returns ``(y,
        counts)``: routed (held experts only) plus shared output, and this
        call's ``COUNTERS`` increments."""
        y, c = common.routed_experts(
            h, h, blk, valid, top_k=self.top_k, held=self.experts_held,
            dtype=self.dtype, scoring="sigmoid", scale=self.route_scale,
            slab=_PAIR_SLAB, impl=impl)
        with _scope("moe_experts"):
            y = y + self._swiglu(h, blk["s_gate"], blk["s_up"],
                                 blk["s_down"])
        step = jnp.int32(1 if decode else 0)
        counts = jnp.stack([
            c["pairs"], c["held"], c["busiest"], step * c["touched"], step]
            + [jnp.int32(0)] * (len(COUNTERS) - 5))      # the other stages'
        return y, counts

    def _blocks(self, params, x, pos, valid, attend, decode: bool = False,
                impl=None):
        """The decoder stack, written once, over tokens ``x`` (T, H) at
        positions ``pos`` (T,).  ``attend(li, blk, q, c_kv, k_r, q_i, k_i,
        w_i) -> (o, keep, counts)`` is the calling path's attention step:
        rotary-applied queries (T, heads, nope + rope), this token's latent
        row (normalised ``c_kv``, rotary-applied ``k_r``), the indexer's
        queries (T, index heads, index dim), key and head weights; ``o`` is
        (T, heads * v_head_dim), ``keep`` what the path carries out of the
        layer; ``impl`` goes to the expert layer.  Returns ``(h, keeps,
        counts)``."""
        nh, T = self.n_head, x.shape[0]
        keeps, counts = [], _no_counts()
        for li, blk in enumerate(params["blocks"]):
            h = self._rms(blk["ln1"], x)
            with _scope("mla"):
                c_q = self._rms(blk["q_a_ln"], self._mm(h, blk["q_a"]))
                q = self._mm(c_q, blk["q_b"]).reshape(T, nh, -1)
                q = jnp.concatenate(
                    [q[..., :self.nope],
                     self._rotary(q[..., self.nope:], pos)], axis=-1)
                kv = self._mm(h, blk["kv_a"])
                c_kv = self._rms(blk["kv_a_ln"], kv[:, :self.kv_rank])
                k_r = self._rotary(kv[:, self.kv_rank:], pos)
            with _scope("dsa_index"):
                r = self.rope

                def rot_first(a):
                    return jnp.concatenate(
                        [self._rotary(a[..., :r], pos), a[..., r:]], axis=-1)

                q_i = rot_first(self._mm(c_q, blk["wq_b"]).reshape(
                    T, self.index_heads, self.index_dim))
                k_i = self._mm(h, blk["wk"])
                mu = k_i.mean(-1, keepdims=True)
                var = ((k_i - mu) ** 2).mean(-1, keepdims=True)
                k_i = rot_first((k_i - mu) * jax.lax.rsqrt(var + 1e-6)
                                * blk["k_ln"]["g"] + blk["k_ln"]["b"])
                w_i = self._mm(h, blk["w_proj"]) \
                    * (self.index_heads ** -0.5 * self.index_dim ** -0.5)
            o, keep, c = attend(li, blk, q, c_kv, k_r, q_i, k_i, w_i)
            keeps.append(keep)
            counts = counts + c
            x = x + self._mm(o, blk["o"])
            h2 = self._rms(blk["ln2"], x)
            if "router" in blk:
                y, c = self._moe(blk, h2, valid, decode, impl)
                counts = counts + c
            else:
                y = self._swiglu(h2, blk["gate"], blk["up"], blk["down"])
            x = x + y
        return self._rms(params["ln_f"], x), keeps, counts

    def _index_scores(self, q_i, k_i, w_i):
        """``I[t, s] = sum_j w[t, j] relu(q_i[t, j] . k_i[s])`` for queries
        (Q, heads, dim) against keys (S, dim): (Q, S)."""
        dots = jax.nn.relu(self._ein("qjd,sd->qjs", q_i, k_i))
        return jnp.einsum("qjs,qj->qs", dots, w_i)

    def _attend_chunks(self, q, k, v, allowed, key_pos, last, scale):
        return common.attend_chunks(q, k, v, allowed, key_pos, last, scale,
                                    self.dtype, _KEY_CHUNK)

    def _softmax_scale(self):
        return 1.0 / np.sqrt(self.nope + self.rope)

    # -- prefill: one sequence, expanded attention over masked dense scores ---
    def _forward_row(self, params, ids, length, base=0, prefix=None,
                     counted=None):
        """One sequence through the stack: ``ids`` (S,) right-padded tokens
        of which ``length`` are real, at positions ``base + i``; ``prefix``
        = per-layer ``(kv rows, ik rows)`` of ``base`` earlier tokens
        (padded to PL rows) that join attention as keys; the first
        ``counted`` tokens (default ``length``) enter the counters and the
        expert layer's groups.  A block of ``_QUERY_BLOCK`` queries whose
        first position is not below ``base + length`` is not attended (no
        indexer scores, no selection, no softmax): its output rows are
        zeros, which nothing reads (padding keys are masked, padding tokens
        are no expert's, the head reads position ``length - 1``).  Returns
        ``(h (S, H), kvs, iks, counts)``: the final hidden states (a real
        position's only), this sequence's per-layer cache rows, and the
        counters' increments: the expert layer's, and a layer's
        ``S // _QUERY_BLOCK`` blocks asked for beside those that ran."""
        S = ids.shape[0]
        qb = min(_QUERY_BLOCK, S)
        if S % qb:
            raise ValueError(f"prefill length {S} is no multiple of {qb}")
        nh, dt = self.n_head, self.dtype
        pos = base + jnp.arange(S)
        PL = 0 if prefix is None else prefix[0][0].shape[0]
        key_pos = jnp.concatenate([jnp.arange(PL), pos])
        key_ok = jnp.concatenate([jnp.arange(PL) < base,
                                  jnp.arange(S) < length])
        scale = self._softmax_scale()

        def attend(li, blk, q, c_kv, k_r, q_i, k_i, w_i):
            # through the cache's type, as decode will read them back
            kv_rows = paged.latent_rows(c_kv, k_r, self.kv_width, dt)
            ik_rows = k_i.astype(dt)
            keys_kv, keys_ik = kv_rows, ik_rows
            if prefix is not None:
                keys_kv = jnp.concatenate([prefix[0][li], kv_rows])
                keys_ik = jnp.concatenate([prefix[1][li], ik_rows])
            c = keys_kv[:, :self.kv_rank]
            kr = keys_kv[:, self.kv_rank:self.kv_rank + self.rope]
            with _scope("mla"):
                kvb = self._mm(c, blk["kv_b"], out=dt).reshape(
                    c.shape[0], nh, self.nope + self.v_dim)
                k = jnp.concatenate(
                    [kvb[..., :self.nope],
                     jnp.broadcast_to(kr[:, None], (c.shape[0], nh,
                                                    self.rope))], axis=-1)
                v = kvb[..., self.nope:]

            def attend_block(qq, qi, wi, t):
                with _scope("dsa_index"):
                    score = self._index_scores(qi, keys_ik, wi)
                with _scope("dsa_select"):
                    ok = (key_pos[None, :] <= t[:, None]) & key_ok[None, :]
                    allowed = self._topk_mask(score, ok, self.index_topk)
                with _scope("mla"):
                    return self._attend_chunks(
                        qq, k, v, lambda lo, hi: allowed[None, :, lo:hi],
                        key_pos, t[-1], scale)

            def block(args):
                return jax.lax.cond(
                    args[0], attend_block,
                    lambda *_: jnp.zeros((qb, nh * self.v_dim), jnp.float32),
                    *args[1:])

            def blocked(a):
                return a.reshape((S // qb, qb) + a.shape[1:])

            # a block past the row's length holds no real query: not run
            live = blocked(pos)[:, 0] < base + length
            o = jax.lax.map(block, (live, blocked(q), blocked(q_i),
                                    blocked(w_i), blocked(pos)))
            counts = _no_counts() \
                .at[COUNTERS.index("prefill_query_blocks")].set(S // qb) \
                .at[COUNTERS.index("prefill_query_blocks_live")].set(
                    live.sum().astype(jnp.int32))
            return o.reshape(S, nh * self.v_dim), (kv_rows, ik_rows), counts

        x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
        h, keeps, counts = self._blocks(
            params, x, pos,
            jnp.arange(S) < (length if counted is None else counted), attend)
        kvs, iks = map(list, zip(*keeps))
        return h, kvs, iks, counts

    def call(self, params, inputs, *, training=False, rng=None):
        """Teacher-forced logits (B, T, V), a sequence at a time."""
        ids = self._ids(inputs)

        def row(seq):
            h, _, _, _ = self._forward_row(params, seq, seq.shape[0])
            return self._mm(h, params["head"])

        return jax.lax.map(row, ids)

    # -- decode: absorbed attention over the selected rows --------------------
    def decode_paged(self, params, state, block_tables, pos, tokens, *,
                     block_len: int, kv_quant: str = "off", impl=None):
        """One token a row against the latent pool (the contract's decode
        step; the selected rows come through the block table by an XLA
        gather).  That gather is the selection's only one: whether a
        selected position is in context and where its row lies are computed
        from ``pos`` and the table.  ``impl`` (``ops/dispatch.resolve_impl``)
        picks the expert layer's kernel (``pallas`` on a TPU, ``interpret``)
        or its ``ragged_dot`` slabs (``xla``, a CPU's).  Returns ``(logits,
        state)``."""
        nh, bl = self.n_head, int(block_len)
        bt = jnp.asarray(block_tables, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        cursor = paged.pool_cursor(bt, pos, bl)
        active = bt[:, 0] != 0           # an idle slot's table is all trash
        S = bt.shape[1] * bl
        kk = min(self.index_topk, S)
        scale = self._softmax_scale()
        in_ctx = jnp.arange(S)[None, :] <= pos[:, None]            # (A, S)

        def attend(li, blk, q, c_kv, k_r, q_i, k_i, w_i):
            kv_pool, ik_pool = paged.latent_append(
                state, li, paged.latent_rows(c_kv, k_r, self.kv_width,
                                             self.dtype), k_i, cursor)
            with _scope("dsa_index"):
                keys = paged.latent_gather(ik_pool, bt)            # (A, S, d)
                dots = jax.nn.relu(self._ein("ajd,asd->ajs", q_i, keys))
                score = jnp.einsum("ajs,aj->as", dots, w_i)
            with _scope("dsa_select"):
                _, sel = jax.lax.top_k(jnp.where(in_ctx, score, -jnp.inf),
                                       kk)
                sel_ok = sel <= pos[:, None]        # in_ctx at sel
                rows = paged.latent_select(kv_pool, bt, sel, bl)   # (A,kk,W)
            with _scope("mla"):
                c = rows[..., :self.kv_rank]
                kr = rows[..., self.kv_rank:self.kv_rank + self.rope]
                kvb = blk["kv_b"].reshape(self.kv_rank, nh,
                                          self.nope + self.v_dim)
                q_abs = self._ein("ahd,rhd->ahr", q[..., :self.nope],
                                  kvb[..., :self.nope])
                att = (self._ein("ahr,asr->ahs", q_abs, c)
                       + self._ein("ahd,asd->ahs", q[..., self.nope:], kr)) \
                    * scale
                att = jnp.where(sel_ok[:, None], att, NEG_INF)
                p = jax.nn.softmax(att, axis=-1)
                lat = self._ein("ahs,asr->ahr", p, c)
                o = self._ein("ahr,rhd->ahd", lat, kvb[..., self.nope:])
            counts = _no_counts() \
                .at[COUNTERS.index("dsa_keys_selected")].set(
                    (sel_ok & active[:, None]).sum().astype(jnp.int32)) \
                .at[COUNTERS.index("dsa_keys_context")].set(
                    jnp.where(active, pos + 1, 0).sum().astype(jnp.int32))
            return o.reshape(-1, nh * self.v_dim), (kv_pool, ik_pool), counts

        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32),
                     axis=0).astype(jnp.float32)
        h, keeps, counts = self._blocks(params, x, pos, active, attend,
                                        decode=True, impl=impl)
        kvs, iks = map(list, zip(*keeps))
        return self._mm(h, params["head"]), dict(
            state, kv=kvs, ik=iks,
            counters=self._bump(state["counters"], counts))

    # -- the paged contract ---------------------------------------------------
    def init_paged_pools(self, n_blocks: int, block_len: int,
                         max_active: int, kv_quant: str = "off"):
        """Zeroed state: ``ops/paged_attention``'s latent pool at this
        model's depth and widths, and the counters."""
        if kv_quant != "off":
            raise ValueError("the latent pool has no quantised format")
        return dict(paged.init_latent_pools(
            self.n_layers, n_blocks, block_len, self.kv_rank, self.rope,
            self.index_dim, self.dtype),
            counters=np.zeros((len(COUNTERS), 2), np.int32))

    def paged_state_bytes(self, state):
        out = paged.pool_bytes({k: v for k, v in state.items()
                                if k != "counters"})
        out["lanes"] += int(np.prod(state["counters"].shape)) * 4
        return out

    def paged_counters(self, state):
        """``COUNTERS`` as Python ints, read from the state on the host (one
        small transfer; the caller owns the state, which must not be in a
        call's hands)."""
        return common.read_counters(state["counters"], COUNTERS)

    def _prefill(self, params, state, prompt, lengths, dest, block_len,
                 prefix_len=None, ptab=None):
        """Rows in sequence inside ONE program (``lax.scan`` carries the
        state), so a batch's temporaries are one row's."""
        prompt = self._ids(prompt)
        lengths = jnp.asarray(lengths, jnp.int32)
        shared = ptab is not None
        xs = (prompt, lengths, jnp.asarray(dest, jnp.int32)) + (
            (jnp.asarray(prefix_len, jnp.int32),
             jnp.asarray(ptab, jnp.int32)) if shared else ())

        def row(st, x):
            ids, n, dst = x[:3]
            base, prefix = 0, None
            if shared:
                base, tab = x[3], x[4][None]
                prefix = ([paged.latent_gather(p, tab)[0] for p in st["kv"]],
                          [paged.latent_gather(p, tab)[0] for p in st["ik"]])
            # a batch's padding row lands in the trash block: not counted
            h, kvs, iks, counts = self._forward_row(
                params, ids, n, base, prefix, jnp.where(dst[0] != 0, n, 0))
            st = dict(paged.latent_commit(st, kvs, iks, dst,
                                          block_len=block_len),
                      counters=self._bump(st["counters"], counts))
            last = jnp.take(h, jnp.maximum(n - 1, 0), axis=0)
            return st, self._mm(last, params["head"])

        return jax.lax.scan(row, state, xs)

    def prefill_paged(self, params, state, prompt, lengths, dest, slots, *,
                      block_len: int, kv_quant: str = "off"):
        return self._prefill(params, state, prompt, lengths, dest,
                             block_len)

    def prefill_shared_paged(self, params, state, suffix, lengths,
                             prefix_len, ptab, dest, slots, *,
                             block_len: int, kv_quant: str = "off"):
        return self._prefill(params, state, suffix, lengths, dest,
                             block_len, prefix_len, ptab)

    # -- contiguous caches (what ``ContinuousBatcher.__init__`` asks of every
    # model; the paged decode over one block a row) ---------------------------
    def init_decode(self, params, prompt, lengths=None,
                    cache_len: Optional[int] = None):
        prompt = self._ids(prompt)
        B, P = prompt.shape
        C = int(cache_len) if cache_len is not None else P
        if C < P:
            raise ValueError(f"cache_len={C} < prompt bucket {P}")
        lengths = jnp.full((B,), P, jnp.int32) if lengths is None \
            else jnp.asarray(lengths, jnp.int32)

        def row(x):
            ids, n = x
            h, kvs, iks, _ = self._forward_row(params, ids, n)
            last = jnp.take(h, jnp.maximum(n - 1, 0), axis=0)
            return kvs, iks, self._mm(last, params["head"])

        kvs, iks, logits0 = jax.lax.map(row, (prompt, lengths))

        def cache(rows):
            return jnp.zeros((B, C, rows.shape[-1]), rows.dtype) \
                .at[:, :P].set(rows)

        return {"pos": lengths, "kv": [cache(r) for r in kvs],
                "ik": [cache(r) for r in iks]}, logits0

    def decode_step(self, params, state, tokens):
        B, C = state["kv"][0].shape[:2]

        def pool(cache):       # row b's cache is block b + 1, block 0 trash
            return jnp.concatenate([jnp.zeros_like(cache[:1]), cache])

        pstate = {"kv": [pool(c) for c in state["kv"]],
                  "ik": [pool(c) for c in state["ik"]],
                  "counters": jnp.zeros((len(COUNTERS), 2), jnp.int32)}
        logits, new = self.decode_paged(
            params, pstate, 1 + jnp.arange(B)[:, None],
            jnp.minimum(state["pos"], C - 1), tokens, block_len=C)
        return logits, {"pos": state["pos"] + 1,
                        "kv": [p[1:] for p in new["kv"]],
                        "ik": [p[1:] for p in new["ik"]]}
