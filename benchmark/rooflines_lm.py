"""Operations and bytes a decode step of a latent-attention, sparse-selection,
routed-expert decoder needs, computed from the configuration file's published keys.

Kept with the benchmark (beside ``rooflines.py``, which an added cell may not edit) so
that no PR that claims a gain can change how the step's roofline share is counted.
The share is the least time the chip could take for the traced window's decode steps
(the larger of operations over peak FLOP/s and bytes over peak bytes/s) over the device
time of the decode program.  Counted per TOKEN-STEP (one token for every active row,
all layers):

- every weight that is not a routed expert's, once (a step reads each once however
  many rows it serves): attention and indexer projections, the router (float32), the
  shared expert, the dense layers' feed-forward, the head's share of the vocabulary;
- one routed expert's three matrices for each held expert that at least one token of
  the step chose (the program's own count, summed over layers);
- the indexer's key of every live context token, each layer (it scores them all);
- ``min(live context, index_topk)`` latent rows (``kv_lora_rank + qk_rope_head_dim``
  values: the padding of the pool's row is not needed bytes) a sequence, each layer;

and two FLOP a weight a token it multiplies, plus the attention and indexer products.
Activations, block tables, the rows a step writes and the embedding rows are left out
(thousands of times smaller).
"""

from __future__ import annotations

import numpy as np


def weight_counts(cfg: dict) -> dict:
    """Numbers of weights by part: ``layer_attention`` (MLA + indexer, a layer),
    ``router`` (a routed layer, float32), ``shared`` (a routed layer), ``expert``
    (ONE routed expert), ``dense_ffn`` (a dense layer), ``head``."""
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    F = cfg["moe_intermediate_size"]
    router_width = (cfg.get("published") or {}).get("n_routed_experts",
                                                    cfg["n_routed_experts"])
    return {
        "layer_attention": H * qr + qr * nh * (nope + rope) + H * (kvr + rope)
        + kvr * nh * (nope + vd) + nh * vd * H
        + qr * ih * idim + H * idim + H * ih,
        "router": H * router_width,
        "shared": 3 * H * F * cfg.get("n_shared_experts", 1),
        "expert": 3 * H * F,
        "dense_ffn": 3 * H * cfg["intermediate_size"],
        "head": H * cfg["vocab_size"]}


def decode_steps_min_seconds(cfg: dict, token_steps: float, tokens: float,
                             experts_touched: float, live_tokens: float,
                             selected_tokens: float, peaks: dict,
                             bytes_per_weight: int = 2,
                             bytes_per_cache_value: int = 2) -> dict:
    """Least seconds for ``token_steps`` decode steps that served ``tokens`` tokens
    in all, touched ``experts_touched`` (expert, layer, step) triples, and saw on
    average ``live_tokens`` context tokens and ``selected_tokens`` =
    sum over sequences of min(context, index_topk) a step.  Returns ``{"seconds",
    "bound", "bytes", "flops"}``."""
    w = weight_counts(cfg)
    L, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    routed = L - dense
    nh, kvr, rope = cfg["num_attention_heads"], cfg["kv_lora_rank"], \
        cfg["qk_rope_head_dim"]
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    held = cfg["n_routed_experts"]
    width = (cfg.get("published") or {}).get("n_routed_experts", held)
    step_weights = (L * w["layer_attention"] + dense * w["dense_ffn"]
                    + routed * w["shared"] + w["head"]) * bytes_per_weight \
        + routed * w["router"] * 4
    cache = L * (live_tokens * idim + selected_tokens * (kvr + rope)) \
        * bytes_per_cache_value
    nbytes = token_steps * (step_weights + cache) \
        + experts_touched * w["expert"] * bytes_per_weight
    per_token = L * w["layer_attention"] + dense * w["dense_ffn"] \
        + routed * (w["shared"] + w["router"]) + w["head"] \
        + routed * cfg["num_experts_per_tok"] * held / width * w["expert"]
    attention = L * (selected_tokens * nh * (2 * kvr + rope)
                     + live_tokens * ih * idim)
    flops = 2.0 * (tokens * per_token + token_steps * attention)
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes), "bytes": nbytes, "flops": flops,
            "bound": "memory" if by_bytes >= by_flops else "compute"}


def live_context(facts: dict, lo: float, hi: float, cap: float = None,
                 points: int = 200) -> float:
    """Context tokens resident, averaged over ``[lo, hi]``: from the client log,
    prompt length + tokens streamed so far of every request between its first and
    its last stamp, each request's count capped at ``cap`` when given (the pool's
    used blocks would overcount: they are reserved for a whole answer)."""
    total = 0.0
    for t in np.linspace(lo, hi, points):
        for r in facts["requests"]:
            st = r["stamps"]
            if not st or not (st[0][0] <= t < st[-1][0]):
                continue
            n = r["prompt_len"] + max(n for ts, n in st if ts <= t)
            total += n if cap is None else min(n, cap)
    return total / points
