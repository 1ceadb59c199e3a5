"""Operations and bytes that a decoder of decayed linear-attention layers beside
block-sparse grouped-query layers needs, for a decode token-step and for a prompt's
prefill, computed from the configuration file's published keys.

Kept with the benchmark (beside ``rooflines.py`` and ``rooflines_lm.py``, which an added
cell may not edit) so that no PR that claims a gain can change how a share is
counted.  Both count the LEAST work, whatever implements it, so that neither share can
read over 100 %:

- a decode TOKEN-STEP (one token for every live row, all layers) reads every layer
  weight and the head once (however many rows it serves); reads and writes the recurrent
  state ``S`` (heads x d x d float32) once a live row a linear layer; reads the
  compressed keys of every live row's context and ``min(topk, context blocks)`` K and V
  blocks (every block of a context of at most ``dense_len``) a live row an attention
  layer.  FLOP beside them: two a weight a token, the recurrence (``k^T v`` and ``q S``),
  the compressed scores, the kept tokens' scores and values.  Activations, block tables,
  the rows a step writes and the embedding rows are left out (thousands of times smaller);
- a PREFILL of ``n`` real positions multiplies every layer weight by every position and
  the head by the last one; runs the recurrence once a position (the chunked form costs
  more: that is the implementation's); and at an attention layer a position of context c
  scores c keys (c <= ``dense_len``) or its visible compressed keys and the tokens of its
  kept blocks.  Padding and masked dense scores are not needed work.
"""

from __future__ import annotations

import numpy as np

SPARSE, LINEAR = "minicpm4", "lightning-attn"


def weight_counts(cfg: dict) -> dict:
    """Numbers of weights: ``sparse`` / ``linear`` (one layer of the kind, its
    feed-forward included), ``head``."""
    H, F = cfg["hidden_size"], cfg["intermediate_size"]
    nh, G, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    lh, ld = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return {"sparse": 3 * H * nh * d + 2 * H * G * d + 3 * H * F,      # q, gate, o; k, v
            "linear": 5 * H * lh * ld + 3 * H * F,                    # q, k, v, gate, o
            "head": H * cfg["vocab_size"]}


def layer_counts(cfg: dict) -> tuple:
    kinds = cfg["mixer_types"]
    return kinds.count(SPARSE), kinds.count(LINEAR)


def kept_tokens(cfg: dict, context):
    """Tokens an attention layer's query at a context of ``context`` tokens (its own
    among them) attends, a key head: all of a context of at most ``dense_len``, else
    ``topk - 1`` whole blocks and the query's own block up to the query."""
    sc = cfg["sparse_config"]
    c = np.asarray(context, np.float64)
    own = (c - 1) % sc["block_size"] + 1
    blocks = np.ceil(c / sc["block_size"])
    kept = (np.minimum(sc["topk"], blocks) - 1) * sc["block_size"] + own
    return np.where(c <= sc["dense_len"], c, kept)


def windows_seen(cfg: dict, context):
    """Complete compressed-key windows that end at or before a query of that context."""
    sc = cfg["sparse_config"]
    c = np.asarray(context, np.float64)
    return np.maximum(np.floor((c - sc["kernel_size"]) / sc["kernel_stride"]) + 1, 0)


def decode_steps_min_seconds(cfg: dict, token_steps: float, tokens: float,
                             contexts, peaks: dict, bytes_per_weight: int = 2,
                             bytes_per_cache_value: int = 2) -> dict:
    """Least seconds for ``token_steps`` decode steps that served ``tokens`` tokens in
    all (``tokens / token_steps`` live rows a step) at the contexts ``contexts`` (one
    entry a live row of an average step).  Returns ``{"seconds", "bound", "bytes",
    "flops"}``."""
    w = weight_counts(cfg)
    n_sparse, n_linear = layer_counts(cfg)
    nh, G, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    lh, ld = cfg["lightning_nh"], cfg["lightning_head_dim"]
    sc = cfg["sparse_config"]
    contexts = np.asarray(contexts, np.float64)
    if not len(contexts):           # no live row seen: the weights' stream alone
        contexts = np.zeros((1,))
    dense = contexts <= sc["dense_len"]
    # whole blocks are read, the query's own too
    blocks = np.ceil(contexts / sc["block_size"])
    blocks = np.where(dense, blocks, np.minimum(sc["topk"], blocks)).mean()
    windows = np.where(dense, 0.0, windows_seen(cfg, contexts)).mean()
    weights = (n_sparse * w["sparse"] + n_linear * w["linear"] + w["head"]) \
        * bytes_per_weight
    per_row = n_linear * 2 * lh * ld * ld * 4 \
        + n_sparse * G * d * bytes_per_cache_value * (
            windows + 2 * blocks * sc["block_size"])
    nbytes = token_steps * weights + tokens * per_row
    per_token = n_sparse * w["sparse"] + n_linear * w["linear"] + w["head"] \
        + n_linear * 2 * lh * ld * ld \
        + n_sparse * nh * d * (windows + 2 * kept_tokens(cfg, contexts).mean())
    flops = 2.0 * tokens * per_token
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes), "bytes": nbytes, "flops": flops,
            "bound": "memory" if by_bytes >= by_flops else "compute"}


def prefill_min_seconds(cfg: dict, lengths, peaks: dict,
                        bytes_per_weight: int = 2) -> dict:
    """Least seconds to prefill prompts of ``lengths`` real positions each (padding is
    not needed work).  Returns ``{"seconds", "bound", "bytes", "flops"}``.  No metric
    reads it yet (the reduced trace cannot pair whole prefill calls with their device
    time, PERF.md section 7): PERF.md's hand readings of one-row calls are made with it."""
    w = weight_counts(cfg)
    n_sparse, n_linear = layer_counts(cfg)
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    lh, ld = cfg["lightning_nh"], cfg["lightning_head_dim"]
    sc = cfg["sparse_config"]
    per_position = n_sparse * w["sparse"] + n_linear * w["linear"] \
        + n_linear * 2 * lh * ld * ld
    flops = nbytes = 0.0
    for n in lengths:
        c = np.arange(1, int(n) + 1, dtype=np.float64)
        scored = np.where(c <= sc["dense_len"], 0.0, windows_seen(cfg, c)) \
            + 2 * kept_tokens(cfg, c)
        flops += 2.0 * (n * per_position + w["head"]
                        + n_sparse * nh * d * scored.sum())
        nbytes += (n_sparse * w["sparse"] + n_linear * w["linear"] + w["head"]) \
            * bytes_per_weight
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes), "bytes": nbytes, "flops": flops,
            "bound": "memory" if by_bytes >= by_flops else "compute"}


def live_contexts(facts: dict, lo: float, hi: float, points: int = 50) -> list:
    """The contexts (prompt length + tokens streamed so far) of the requests in flight,
    sampled at ``points`` instants of ``[lo, hi]``: one entry a (request, instant), from
    the client log (a request is in flight between its first and its last stamp)."""
    out = []
    for t in np.linspace(lo, hi, points):
        for r in facts["requests"]:
            st = r["stamps"]
            if st and st[0][0] <= t < st[-1][0]:
                out.append(r["prompt_len"] + max(n for ts, n in st if ts <= t))
    return out
