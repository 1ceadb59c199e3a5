"""TFPark text Keras-model family, rebuilt natively (VERDICT r2 row 32).

Reference parity: pyzoo/zoo/tfpark/text/keras/{ner.py, pos_tagging.py,
intent_extraction.py} — which wrap nlp-architect graphs (word+char BiLSTM
taggers with a CRF head; a joint intent/entity model).  Here the graphs are
built from native layers and train through the Estimator; the CRF head is a
real linear-chain CRF (nn/layers/crf.py) rather than a wrapped dependency.

Input conventions match the reference:
  NER / SequenceTagger: [word_ids (B, T), char_ids (B, T, W)]
  IntentEntity:         [word_ids (B, T), char_ids (B, T, W)]

PR 12 (continuous batching) adds ``TransformerLM`` — a decoder-only
autoregressive generator with a step-wise decode API: ``init_decode``
prefills a FIXED-LENGTH KV cache from a (right-padded) prompt batch and
``decode_step`` appends one token per call, so the serving scheduler can
step a churning slot batch through one compiled program per cache bucket.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.estimator.estimator import Estimator
from analytics_zoo_tpu.nn.layers.core import Dense, Dropout, Embedding
from analytics_zoo_tpu.ops import paged_attention as paged
from analytics_zoo_tpu.nn.layers.crf import CRF
from analytics_zoo_tpu.nn.layers.recurrent import LSTM, Bidirectional
from analytics_zoo_tpu.nn.module import Layer
from analytics_zoo_tpu.nn.optimizers import Adam


class _WordCharEncoder(Layer):
    """Shared tagger trunk: word embedding + char-BiLSTM word features ->
    sentence BiLSTM states (B, T, 2*lstm_dim).  word_length (when given)
    validates the char input width against the configured value."""

    def __init__(self, word_vocab_size, char_vocab_size, word_emb_dim=100,
                 char_emb_dim=30, lstm_dim=100, dropout=0.5,
                 word_length=None, **kwargs):
        super().__init__(**kwargs)
        self.word_emb = Embedding(word_vocab_size, word_emb_dim,
                                  name=self.name + "_wemb")
        self.char_emb = Embedding(char_vocab_size, char_emb_dim,
                                  name=self.name + "_cemb")
        self.char_lstm = Bidirectional(
            LSTM(char_emb_dim, inner_activation="sigmoid"),
            name=self.name + "_clstm")
        self.sent_lstm = Bidirectional(
            LSTM(lstm_dim, inner_activation="sigmoid",
                 return_sequences=True), name=self.name + "_slstm")
        self.drop = Dropout(dropout, name=self.name + "_drop")
        self.dims = (word_emb_dim, char_emb_dim, lstm_dim)
        self.word_length = word_length

    def build(self, rng, input_shape):
        word_d, char_d, lstm_d = self.dims
        r = jax.random.split(rng, 4)
        return {
            "wemb": self.word_emb.build(r[0], None),
            "cemb": self.char_emb.build(r[1], None),
            "clstm": self.char_lstm.build(r[2], (None, char_d)),
            "slstm": self.sent_lstm.build(r[3],
                                          (None, word_d + 2 * char_d)),
        }

    def call(self, params, inputs, *, training=False, rng=None):
        word_ids, char_ids = inputs
        B, T = word_ids.shape[:2]
        W = char_ids.shape[-1]
        if self.word_length is not None and W != self.word_length:
            raise ValueError(
                f"char input width {W} != configured word_length "
                f"{self.word_length}")
        w = self.word_emb.call(params["wemb"], word_ids)          # (B,T,Dw)
        c = self.char_emb.call(params["cemb"],
                               char_ids.reshape(B * T, W))        # (BT,W,Dc)
        cw = self.char_lstm.call(params["clstm"], c)              # (BT,2Dc)
        cw = cw.reshape(B, T, -1)
        h = jnp.concatenate([w, cw], axis=-1)
        h = self.drop.call({}, h, training=training, rng=rng)
        return self.sent_lstm.call(params["slstm"], h,
                                   training=training, rng=rng)    # (B,T,2H)


class _TaggerModel(Layer):
    """Encoder + per-head token projections (+ CRF for head 0)."""

    def __init__(self, head_dims: Tuple[int, ...], use_crf: bool = True,
                 pooled_head: Optional[int] = None, **enc_kw):
        super().__init__()
        self.encoder = _WordCharEncoder(name=self.name + "_enc", **enc_kw)
        self.head_dims = tuple(head_dims)
        self.heads = [Dense(d, name=f"{self.name}_head{i}")
                      for i, d in enumerate(self.head_dims)]
        self.use_crf = use_crf
        self.pooled_head = pooled_head        # head index fed pooled state
        self.crf = CRF(self.head_dims[0], name=self.name + "_crf") \
            if use_crf else None

    def build(self, rng, input_shape):
        r = jax.random.split(rng, 2 + len(self.heads))
        lstm_out = 2 * self.encoder.dims[2]
        p = {"enc": self.encoder.build(r[0], input_shape)}
        for i, head in enumerate(self.heads):
            p[f"head{i}"] = head.build(r[2 + i], (None, lstm_out))
        if self.crf is not None:
            p["crf"] = self.crf.build(r[1], (None, self.head_dims[0]))
        return p

    def call(self, params, inputs, *, training=False, rng=None):
        h = self.encoder.call(params["enc"], inputs, training=training,
                              rng=rng)                            # (B,T,2H)
        outs = []
        for i, head in enumerate(self.heads):
            x = h.mean(axis=1) if i == self.pooled_head else h
            outs.append(head.call(params[f"head{i}"], x))
        if self.crf is not None:
            # CRF potentials ride along in y_pred (batch-broadcast) so the
            # Estimator loss differentiates them — the loss callable only
            # sees (y_pred, y_true), never the param pytree
            B = outs[0].shape[0]
            cp = params["crf"]
            outs += [jnp.broadcast_to(cp["transitions"],
                                      (B,) + cp["transitions"].shape),
                     jnp.broadcast_to(cp["start"], (B,) + cp["start"].shape),
                     jnp.broadcast_to(cp["end"], (B,) + cp["end"].shape)]
        return outs[0] if len(outs) == 1 else tuple(outs)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _round_operands(src, dtype):
    """``TransformerLM.matmul_operands``' one program: the weights rounded
    to ``dtype``, the head transposed to (hidden, vocab)."""
    out = jax.tree.map(lambda w: w.astype(dtype), src)
    if "head" in out:
        out["head"] = out["head"].T
    return out


class TransformerLM(Layer):
    """Decoder-only transformer language model with a KV-cache step API
    (the GPT-style generator the serving plane's continuous batcher
    drives).  Pre-LN blocks, learned positional embeddings, weight-tied
    output head.  The decoder block is written ONCE (``_blocks``); every
    forward path below supplies only its attention step.

    Monolithic paths: ``call(params, ids)`` -> (B, T, V) logits (teacher
    forcing / training), ``generate`` -> one ``lax.scan`` greedy rollout
    (the batch-in/batch-out baseline).  Step-wise paths (PR 12):

    - ``init_decode(params, prompt, lengths, cache_len) -> (state,
      logits0)``: prefill.  ``prompt`` (B, P) is right-padded; ``lengths``
      (B,) true lengths.  The per-layer K/V caches are allocated at
      ``cache_len`` (>= P, the pow-2 capacity bucket) so every later
      ``decode_step`` runs one fixed-shape program; ``logits0`` is each
      row's next-token logits at its last REAL prompt position.
    - ``decode_step(params, state, tokens) -> (logits, state)``: write the
      token's K/V at each row's own cursor (``state["pos"]``), attend over
      the cache positions written so far, advance the cursor.  Every state
      leaf keeps a leading batch (slot) axis for ``.at[slot].set``
      insertion.

    The PAGED CONTRACT (PR 18, 30) — what ``ContinuousBatcher(paged=True)``
    asks of a model.  KV lives in a fixed block pool whose device format
    belongs to ``ops/paged_attention``; the caller holds the state as an
    opaque pytree, and each row carries a block table.

    - ``init_paged_pools(n_blocks, block_len, max_active, kv_quant)`` ->
      the zeroed state.
    - ``prefill_paged(params, state, prompt, lengths, dest, slots, ...)``
      -> ``(state, logits0)``: ``init_decode``'s prompt forward (the same
      core, so both prefills are bitwise-identical) with the K/V committed
      to the blocks ``dest`` names.
    - ``prefill_shared_paged(params, state, suffix, lengths, prefix_len,
      ptab, dest, slots, ...)`` -> ``(state, logits0)``: suffix-only
      prefill for prefix-cache hits — the shared prefix's K/V is gathered
      from the blocks ``ptab`` names, only the suffix runs through the
      stack (the prefill work prefix sharing saves).
    - ``decode_paged(params, state, block_tables, pos, tokens, ...)`` ->
      ``(logits, state)``: one token per row, appended through the block
      table and attended by the ``paged_attention`` kernel.
    - ``paged_state_bytes(state)`` -> the state's bytes by accounting
      class (``paged_pool`` / ``scales`` / ``lanes``).

    WHICH TREE ``params`` IS (PR 31).  Every path takes the float32 tree
    ``build`` returns; the step-wise ones (``init_decode``,
    ``decode_step``, the three paged programs) are served
    ``matmul_operands(params, dtype)`` instead, the optional sixth method
    of the contract: the same tree with each matmul weight rounded ONCE
    to the type the backend's matmul would round it to in every call
    (``ops/dispatch.matmul_operand_dtype``: bfloat16 on a TPU at default
    precision, None = the float32 tree itself on a CPU), and the tied
    head as a leaf of its own.  ``_lin`` / ``_logits`` tell the two apart
    by the weight's dtype: there is one decoder block and no flag.
    Training, checkpoints and the weight store only ever see float32."""

    def __init__(self, vocab_size: int, hidden: int = 64, n_head: int = 4,
                 n_layers: int = 2, max_len: int = 512,
                 initializer_range: float = 0.02, **kwargs):
        super().__init__(**kwargs)
        if hidden % n_head:
            raise ValueError(f"hidden={hidden} not divisible by "
                             f"n_head={n_head}")
        self.vocab_size = int(vocab_size)
        self.hidden = int(hidden)
        self.n_head = int(n_head)
        self.n_layers = int(n_layers)
        self.max_len = int(max_len)
        self.std = float(initializer_range)
        self._declared_input_shape = (None,)

    def build(self, rng, input_shape=None):
        H, V = self.hidden, self.vocab_size
        r = jax.random.split(rng, 2 + 4 * self.n_layers)
        std = self.std

        def dense(key, d_in, d_out):
            return {"W": std * jax.random.normal(key, (d_in, d_out),
                                                 jnp.float32),
                    "b": jnp.zeros((d_out,), jnp.float32)}

        p = {"embed": std * jax.random.normal(r[0], (V, H), jnp.float32),
             "pos": std * jax.random.normal(r[1], (self.max_len, H),
                                            jnp.float32),
             "ln_f": {"g": jnp.ones((H,), jnp.float32),
                      "b": jnp.zeros((H,), jnp.float32)},
             "blocks": []}
        for i in range(self.n_layers):
            k = r[2 + 4 * i: 6 + 4 * i]
            p["blocks"].append({
                "ln1": {"g": jnp.ones((H,)), "b": jnp.zeros((H,))},
                "qkv": dense(k[0], H, 3 * H),
                "proj": dense(k[1], H, H),
                "ln2": {"g": jnp.ones((H,)), "b": jnp.zeros((H,))},
                "fc1": dense(k[2], H, 4 * H),
                "fc2": dense(k[3], 4 * H, H)})
        return p

    # -- shared pieces --------------------------------------------------------
    @staticmethod
    def _ln(p, x, eps=1e-5):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]

    @staticmethod
    def _mxu(x, W):
        """``x @ W`` accumulated in float32.  A ``W`` that arrives in
        bfloat16 is a ``matmul_operands`` leaf, rounded once at load: the
        activation is rounded the way the MXU pass over float32 operands
        rounds it, so the products are that pass's own.  A float32 ``W``
        is multiplied as it is."""
        if W.dtype == jnp.bfloat16:
            x = x.astype(jnp.bfloat16)
        return jnp.matmul(x, W, preferred_element_type=jnp.float32)

    def _lin(self, p, x):
        return self._mxu(x, p["W"]) + p["b"]

    def _heads(self, x):
        # (..., H) -> (..., n_head, head_dim)
        return x.reshape(x.shape[:-1] + (self.n_head,
                                         self.hidden // self.n_head))

    def _logits(self, params, h):
        # weight-tied head: logits = h @ embed.T (the operand tree brings
        # the transpose ready-made as ``head``)
        head = params.get("head")
        return self._mxu(h, params["embed"].T if head is None else head)

    @staticmethod
    def _ids(x):
        """Token ids as an int32 (B, T) array (a trailing unit axis, as a
        feature column arrives, is dropped)."""
        x = jnp.asarray(x)
        if x.ndim == 3 and x.shape[-1] == 1:
            x = x[..., 0]
        return x.astype(jnp.int32)

    def _blocks(self, params, x, attend):
        """The decoder stack, written once: per block LayerNorm, qkv,
        heads, ``attend``, proj, LayerNorm, fc1-GELU-fc2, then the final
        LayerNorm.  ``attend(li, q, k, v) -> (o, keep)`` is the calling
        path's attention step over (..., n_head, head_dim) heads; ``keep``
        is what that path carries out of layer ``li`` (the new K/V, the
        updated cache, the pool's leaves).  Returns ``(h, keeps)``."""
        keeps = []
        for li, blk in enumerate(params["blocks"]):
            h = self._ln(blk["ln1"], x)
            q, k, v = jnp.split(self._lin(blk["qkv"], h), 3, axis=-1)
            o, keep = attend(li, self._heads(q), self._heads(k),
                             self._heads(v))
            keeps.append(keep)
            x = x + self._lin(blk["proj"], o.reshape(x.shape))
            h2 = self._ln(blk["ln2"], x)
            x = x + self._lin(blk["fc2"],
                              jax.nn.gelu(self._lin(blk["fc1"], h2)))
        return self._ln(params["ln_f"], x), keeps

    @staticmethod
    def _attend(q, k, v, mask):
        """Softmax attention of (B, Q, nh, hd) queries over (B, K, nh, hd)
        keys under a boolean (B or 1, Q, K) mask."""
        scale = 1.0 / np.sqrt(q.shape[-1])
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        att = jnp.where(mask[:, None], att, -1e30)
        att = jax.nn.softmax(att, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", att, v)

    def _last_logits(self, params, h, lengths):
        # each row's next-token logits live at its LAST REAL position
        last = jnp.take_along_axis(
            h, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
        return self._logits(params, last)

    # -- the weights' operand form (PR 31) ------------------------------------
    _MATMULS = ("qkv", "proj", "fc1", "fc2")

    def matmul_operands(self, params, dtype):
        """The tree the step-wise programs read, made ONCE per weight
        load: every float32 ``blocks[i].{qkv,proj,fc1,fc2}.W`` rounded to
        ``dtype`` (what ``ops/dispatch.matmul_operand_dtype`` says the
        backend's matmul rounds it to on every call anyway), and one new
        leaf ``head``, the tied output head ``embed.T`` in ``dtype`` laid
        out (hidden, vocab) as ``_logits`` multiplies it.  Every other
        leaf (``embed`` for the gather, ``pos``, LayerNorm, biases) is the
        SAME array as in ``params``, shared; a ``W`` that is not float32
        stays as it is; the float32 ``W``s are not in the tree.  The
        copies keep their source's sharding.  ``dtype=None`` is the
        identity: ``params`` itself."""
        if dtype is None:
            return params

        def f32(a):
            return a.dtype == jnp.float32

        src = {"blocks": [{n: blk[n]["W"] for n in self._MATMULS
                           if f32(blk[n]["W"])}
                          for blk in params["blocks"]]}
        if f32(params["embed"]):
            src["head"] = params["embed"]
        cast = _round_operands(src, dtype)
        tree = dict(params, blocks=[
            dict(blk, **{n: dict(blk[n], W=w) for n, w in ws.items()})
            for blk, ws in zip(params["blocks"], cast["blocks"])])
        if "head" in cast:
            tree["head"] = cast["head"]
        return tree

    # -- monolithic forward (teacher forcing / training) ----------------------
    def call(self, params, inputs, *, training=False, rng=None):
        ids = self._ids(inputs)
        T = ids.shape[1]
        x = jnp.take(params["embed"], ids, axis=0) + params["pos"][:T]
        causal = jnp.tril(jnp.ones((1, T, T), bool))
        h, _ = self._blocks(
            params, x,
            lambda li, q, k, v: (self._attend(q, k, v, causal), None))
        return self._logits(params, h)

    # -- step-wise decode (PR 12) ---------------------------------------------
    def _prefill_core(self, params, prompt, lengths):
        """Shared prompt forward: the exact math ``init_decode`` has always
        run, shared with the paged prefill (PR 18) so the two stay
        BITWISE-identical.  Returns ``(ks, vs, logits0, lengths)`` with
        ``ks``/``vs`` per-layer (B, P, nh, hd)."""
        prompt = self._ids(prompt)
        B, P = prompt.shape
        lengths = (jnp.full((B,), P, jnp.int32) if lengths is None
                   else jnp.asarray(lengths, jnp.int32))
        x = jnp.take(params["embed"], prompt, axis=0) + params["pos"][:P]
        pos_idx = jnp.arange(P)
        # causal within the prompt AND key < row length (padding masked)
        mask = (pos_idx[None, :, None] >= pos_idx[None, None, :]) \
            & (pos_idx[None, None, :] < lengths[:, None, None])  # (B,P,P)
        h, kv = self._blocks(
            params, x,
            lambda li, q, k, v: (self._attend(q, k, v, mask), (k, v)))
        ks, vs = map(list, zip(*kv))
        return ks, vs, self._last_logits(params, h, lengths), lengths

    def init_decode(self, params, prompt, lengths=None,
                    cache_len: Optional[int] = None):
        """Prefill: run the prompt through the stack once, parking K/V in
        ``cache_len``-capacity caches.  Padded positions (>= the row's
        length) are masked out of attention and overwritten later by
        generated tokens — the cache layout stays gap-free because the
        cursor starts AT the row's length."""
        B, P = self._ids(prompt).shape
        C = int(cache_len) if cache_len is not None else int(P)
        if C < P:
            raise ValueError(f"cache_len={C} < prompt bucket {P}")
        if C > self.max_len:
            raise ValueError(f"cache_len={C} > max_len={self.max_len}")
        nh, hd = self.n_head, self.hidden // self.n_head
        ks, vs, logits0, lengths = self._prefill_core(params, prompt,
                                                      lengths)
        state = {"pos": lengths, "k": [], "v": []}
        for k, v in zip(ks, vs):
            state["k"].append(
                jnp.zeros((B, C, nh, hd), jnp.float32).at[:, :P].set(k))
            state["v"].append(
                jnp.zeros((B, C, nh, hd), jnp.float32).at[:, :P].set(v))
        return state, logits0

    def decode_step(self, params, state, tokens):
        """One token for every row: write K/V at the row cursor, attend
        over the written prefix, advance.  (B,)-shaped ``tokens`` in,
        ``(logits (B, V), new_state)`` out — one fixed-shape program per
        cache bucket, no retracing as rows churn."""
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = state["pos"]                         # (B,) cursor
        C = state["k"][0].shape[1]
        rows = jnp.arange(tokens.shape[0])
        # clamp the cursor so a full cache row keeps overwriting its last
        # slot instead of indexing out of bounds (the scheduler retires
        # rows at capacity; this is the belt under that suspender)
        wpos = jnp.minimum(pos, C - 1)
        x = jnp.take(params["embed"], tokens, axis=0) \
            + jnp.take(params["pos"], jnp.minimum(pos, self.max_len - 1),
                       axis=0)                     # (B, H)
        key_idx = jnp.arange(C)

        def attend(li, q, k, v):
            kc = state["k"][li].at[rows, wpos].set(k)
            vc = state["v"][li].at[rows, wpos].set(v)
            scale = 1.0 / np.sqrt(q.shape[-1])
            att = jnp.einsum("bhd,bkhd->bhk", q, kc) * scale
            valid = key_idx[None] <= wpos[:, None]          # (B, C)
            att = jnp.where(valid[:, None], att, -1e30)
            att = jax.nn.softmax(att, axis=-1)
            return jnp.einsum("bhk,bkhd->bhd", att, vc), (kc, vc)

        h, kv = self._blocks(params, x, attend)
        ks, vs = map(list, zip(*kv))
        return self._logits(params, h), {"pos": pos + 1, "k": ks, "v": vs}

    # -- the paged contract (PR 18, 30) ---------------------------------------
    def init_paged_pools(self, n_blocks: int, block_len: int,
                         max_active: int, kv_quant: str = "off"):
        """Zeroed state for the paged batcher (``n_blocks`` counts the
        trash block): ``ops/paged_attention``'s pool at this model's
        depth and heads."""
        return paged.init_pools(self.n_layers, n_blocks, block_len,
                                self.n_head, self.hidden // self.n_head,
                                max_active, kv_quant)

    def paged_state_bytes(self, state):
        """``state``'s bytes (arrays or their shapes) by accounting class
        for the resource ledger."""
        return paged.pool_bytes(state)

    def prefill_paged(self, params, state, prompt, lengths, dest, slots, *,
                      block_len: int, kv_quant: str = "off"):
        """Paged prefill: ``init_decode``'s prompt forward, its raw K/V
        committed to the pool blocks ``dest`` (rows, ceil(P / block_len))
        names instead of parked in caches; ``slots`` (rows,) are the
        decode slots the rows will run in.  Returns ``(state,
        logits0)``."""
        ks, vs, logits0, lengths = self._prefill_core(params, prompt,
                                                      lengths)
        return paged.pool_commit(state, ks, vs, lengths, dest, slots,
                                 block_len=block_len,
                                 kv_quant=kv_quant), logits0

    def prefill_shared_paged(self, params, state, suffix, lengths,
                             prefix_len, ptab, dest, slots, *,
                             block_len: int, kv_quant: str = "off"):
        """Suffix-only prefill for prefix-cache hits: the shared prefix's
        K/V, gathered from the pool blocks ``ptab`` (rows, n) names, joins
        attention as extra keys; only the ``suffix`` tokens run through
        the stack, and only their K/V is committed (to ``dest``).  Rows'
        true prefix lengths ``prefix_len`` (B,) mask the gather padding;
        suffix positions embed at ``prefix_len + i``; ``lengths`` are the
        suffix lengths.  Returns ``(state, logits0)``."""
        prefix_k, prefix_v = paged.pool_gather(state, ptab, self.n_head)
        suffix = self._ids(suffix)
        B, S = suffix.shape
        lengths = jnp.asarray(lengths, jnp.int32)
        prefix_len = jnp.asarray(prefix_len, jnp.int32)
        PL = prefix_k[0].shape[1]
        gpos = jnp.minimum(prefix_len[:, None] + jnp.arange(S),
                           self.max_len - 1)             # (B, S) global pos
        x = jnp.take(params["embed"], suffix, axis=0) \
            + jnp.take(params["pos"], gpos, axis=0)
        qi = jnp.arange(S)
        # keys = [prefix (PL) | suffix (S)]: prefix key j valid iff
        # j < prefix_len[row]; suffix key js valid iff causal AND real
        pmask = jnp.arange(PL)[None, None, :] \
            < prefix_len[:, None, None]                  # (B, 1, PL) -> bcast
        smask = (qi[None, :, None] >= qi[None, None, :]) \
            & (qi[None, None, :] < lengths[:, None, None])   # (B, S, S)
        mask = jnp.concatenate(
            [jnp.broadcast_to(pmask, (B, S, PL)), smask], axis=2)

        def attend(li, q, k, v):
            kk = jnp.concatenate([prefix_k[li], k], axis=1)
            vv = jnp.concatenate([prefix_v[li], v], axis=1)
            return self._attend(q, kk, vv, mask), (k, v)

        h, kv = self._blocks(params, x, attend)
        ks, vs = map(list, zip(*kv))
        logits0 = self._last_logits(params, h, lengths)
        return paged.pool_commit(state, ks, vs, lengths, dest, slots,
                                 block_len=block_len,
                                 kv_quant=kv_quant), logits0

    def decode_paged(self, params, pstate, block_tables, pos, tokens, *,
                     block_len: int, kv_quant: str = "off", impl=None):
        """One token per row against the block pool: ``decode_step``'s
        math with the cache write routed through each row's block table
        and the read through the ``paged_attention`` kernel
        (``ops/paged_attention.pool_append_attend``).  Inactive rows point
        their whole table at the trash block, so their writes land
        harmlessly and their attention reads nothing (a zero row).
        Returns ``(logits, new_pstate)`` — the caller advances ``pos``."""
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        bt = jnp.asarray(block_tables, jnp.int32)
        cursor = paged.pool_cursor(bt, pos, int(block_len))
        x = jnp.take(params["embed"], tokens, axis=0) \
            + jnp.take(params["pos"], jnp.minimum(pos, self.max_len - 1),
                       axis=0)
        h, layers = self._blocks(
            params, x,
            lambda li, q, k, v: paged.pool_append_attend(
                pstate, li, q, k, v, cursor, bt, pos, kv_quant=kv_quant,
                impl=impl))
        return self._logits(params, h), {
            name: [leaves[name] for leaves in layers] for name in pstate}

    # -- monolithic greedy rollout (batch-in/batch-out baseline) --------------
    def generate(self, params, prompt, max_tokens: int = 32,
                 eos_id: Optional[int] = None, lengths=None,
                 return_lengths: bool = False):
        """Greedy decode under ONE ``lax.scan`` — the static-batching
        baseline the bench A/Bs against: the whole batch holds until the
        slowest row has run all ``max_tokens`` steps.  Same EOS contract
        as ``Seq2seq.infer``: post-EOS tokens freeze to ``eos_id`` and
        ``return_lengths`` yields per-row generated lengths."""
        prompt = np.asarray(prompt)
        B, P = prompt.shape
        # the KV cache cannot outgrow max_len: clamp the budget to the
        # remaining capacity instead of silently overwriting the last
        # slot for every overflow token (decode_step's cursor clamp is a
        # belt for the serving scheduler, not a rollout contract)
        room = self.max_len - P
        if room < 1:
            raise ValueError(f"prompt length {P} leaves no decode room "
                             f"(max_len={self.max_len})")
        max_tokens = min(int(max_tokens), room)
        cap = P + max_tokens
        state, logits0 = self.init_decode(params, prompt, lengths=lengths,
                                          cache_len=cap)
        tok0 = jnp.argmax(logits0, axis=-1).astype(jnp.int32)
        stop = -1 if eos_id is None else int(eos_id)
        done0 = (tok0 == stop)

        def body(carry, _):
            st, tok, done = carry
            logits, new_st = self.decode_step(params, st, tok)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if eos_id is not None:
                nxt = jnp.where(done, jnp.int32(stop), nxt)
            return (new_st, nxt, done | (nxt == stop)), (nxt, done | (nxt == stop))

        steps = max(int(max_tokens) - 1, 0)
        if steps:
            _, (toks, dones) = jax.lax.scan(body, (state, tok0, done0),
                                            None, length=steps)
            out = np.concatenate([np.asarray(tok0)[:, None],
                                  np.asarray(jnp.swapaxes(toks, 0, 1))],
                                 axis=1)
            done_steps = np.asarray(jnp.sum(dones, axis=0)) \
                + np.asarray(done0).astype(np.int64)
        else:
            out = np.asarray(tok0)[:, None]
            done_steps = np.asarray(done0).astype(np.int64)
        lengths_out = (int(max_tokens) - done_steps).astype(np.int64)
        if return_lengths:
            return out, lengths_out
        return out


class _TextModelBase:
    """fit/predict plumbing shared by the text models."""

    def __init__(self, model: _TaggerModel, loss, optimizer=None, ctx=None):
        self.model = model
        self.estimator = Estimator(model,
                                   optimizer=optimizer or Adam(lr=1e-3),
                                   loss=loss, ctx=ctx)

    def fit(self, x, y, *, batch_size=32, epochs=1, **kw):
        return self.estimator.fit(list(x), y, batch_size=batch_size,
                                  epochs=epochs, **kw)

    def predict(self, x, *, batch_size=32):
        return self.estimator.predict(list(x), batch_size=batch_size)


class NER(_TextModelBase):
    """BiLSTM + CRF named-entity tagger (ner.py parity).

    fit labels: (B, T) int tags.  predict returns Viterbi tag paths (B, T)."""

    def __init__(self, num_entities, word_vocab_size, char_vocab_size,
                 word_length=12, word_emb_dim=100, char_emb_dim=30,
                 tagger_lstm_dim=100, dropout=0.5, optimizer=None, ctx=None):
        model = _TaggerModel((num_entities,), use_crf=True,
                             word_vocab_size=word_vocab_size,
                             char_vocab_size=char_vocab_size,
                             word_emb_dim=word_emb_dim,
                             char_emb_dim=char_emb_dim,
                             lstm_dim=tagger_lstm_dim, dropout=dropout,
                             word_length=word_length)

        def crf_loss(y_pred, y_true):
            emissions, trans, start, end = y_pred
            tags = jnp.asarray(y_true).astype(jnp.int32)
            if tags.ndim == 3:
                tags = tags[..., 0]
            crf_params = {"transitions": trans[0], "start": start[0],
                          "end": end[0]}
            return model.crf.neg_log_likelihood(crf_params, emissions, tags)

        super().__init__(model, crf_loss, optimizer, ctx)

    def predict(self, x, *, batch_size=32):
        out = super().predict(x, batch_size=batch_size)
        emissions = out[0]
        params = jax.device_get(self.estimator.params)
        return np.asarray(self.model.crf.decode(params["crf"],
                                                jnp.asarray(emissions)))


class SequenceTagger(_TextModelBase):
    """Joint POS + chunk tagger (pos_tagging.py parity): two per-token
    softmax heads.  fit labels: (B, T, 2) int [pos, chunk]."""

    def __init__(self, num_pos_labels, num_chunk_labels, word_vocab_size,
                 char_vocab_size, word_length=12, word_emb_dim=100,
                 char_emb_dim=30, tagger_lstm_dim=100, dropout=0.5,
                 optimizer=None, ctx=None):
        model = _TaggerModel((num_pos_labels, num_chunk_labels),
                             use_crf=False,
                             word_vocab_size=word_vocab_size,
                             char_vocab_size=char_vocab_size,
                             word_emb_dim=word_emb_dim,
                             char_emb_dim=char_emb_dim,
                             lstm_dim=tagger_lstm_dim, dropout=dropout,
                             word_length=word_length)

        def joint_loss(y_pred, y_true):
            pos_logits, chunk_logits = y_pred
            t = jnp.asarray(y_true).astype(jnp.int32)
            lp = jax.nn.log_softmax(pos_logits, axis=-1)
            lc = jax.nn.log_softmax(chunk_logits, axis=-1)
            nll_p = -jnp.take_along_axis(lp, t[..., :1], axis=-1)[..., 0]
            nll_c = -jnp.take_along_axis(lc, t[..., 1:2], axis=-1)[..., 0]
            return (nll_p + nll_c).mean(axis=-1)

        super().__init__(model, joint_loss, optimizer, ctx)


class IntentEntity(_TextModelBase):
    """Joint intent classification + entity extraction
    (intent_extraction.py parity): a pooled intent head + per-token entity
    head.  fit labels: (B, 1 + T) int [intent, entity tags...]."""

    def __init__(self, num_intents, num_entities, word_vocab_size,
                 char_vocab_size, word_length=12, word_emb_dim=100,
                 char_emb_dim=30, tagger_lstm_dim=100, dropout=0.5,
                 optimizer=None, ctx=None):
        model = _TaggerModel((num_entities, num_intents), use_crf=False,
                             pooled_head=1,
                             word_vocab_size=word_vocab_size,
                             char_vocab_size=char_vocab_size,
                             word_emb_dim=word_emb_dim,
                             char_emb_dim=char_emb_dim,
                             lstm_dim=tagger_lstm_dim, dropout=dropout,
                             word_length=word_length)

        def joint_loss(y_pred, y_true):
            ent_logits, intent_logits = y_pred
            t = jnp.asarray(y_true).astype(jnp.int32)
            intent, tags = t[:, 0], t[:, 1:]
            li = jax.nn.log_softmax(intent_logits, axis=-1)
            nll_i = -jnp.take_along_axis(li, intent[:, None], axis=-1)[:, 0]
            le = jax.nn.log_softmax(ent_logits, axis=-1)
            nll_e = -jnp.take_along_axis(le, tags[..., None],
                                         axis=-1)[..., 0].mean(axis=-1)
            return nll_i + nll_e

        super().__init__(model, joint_loss, optimizer, ctx)
