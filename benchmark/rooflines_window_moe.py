"""Operations and bytes that a decode token-step of a grouped-query decoder with window
and full attention layers and a routed expert layer needs, computed from the
configuration file's published keys.

Kept with the benchmark (beside ``rooflines.py``, ``rooflines_lm.py`` and
``rooflines_sparse_linear.py``, which an added cell may not edit) so that no PR that
claims a gain can change how the step's share is counted.  It counts the LEAST work,
whatever implements it, so that the share cannot read over 100 %.  A decode TOKEN-STEP
(one token for every live row, all layers) reads:

- every weight that is not a routed expert's once (attention projections, the router in
  float32, the head), however many rows it serves;
- one routed expert's three matrices for each expert that at least one token of the step
  chose (the program's own count ``moe_experts_touched``, summed over layers and steps);
- a full layer's K and V of every live row's whole context, and a window layer's K and V
  of its last ``min(context, sliding_window_size)`` positions, a live row;

and does two FLOP a weight a token it multiplies (the ``top_k`` experts a token chose among
them), plus the attention's scores and values over the keys above.  Activations, block
tables, the rows a step writes and the embedding rows are left out (thousands of times
smaller).
"""

from __future__ import annotations

import numpy as np


def weight_counts(cfg: dict) -> dict:
    """Numbers of weights: ``attention`` (q, k, v, o of one layer), ``router`` (one layer,
    float32), ``expert`` (ONE routed expert's three matrices), ``head``."""
    H, nh, G, d = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    return {"attention": 2 * H * nh * d + 2 * H * G * d,
            "router": H * cfg["moe_num_primary_experts"],
            "expert": 3 * H * cfg["moe_ffn_hidden_size"],
            "head": H * cfg["vocab_size"]}


def layer_counts(cfg: dict) -> tuple:
    """``(full layers, window layers)``."""
    window = sum(1 for w in cfg["sliding_window_layout"] if w)
    return cfg["num_hidden_layers"] - window, window


def decode_steps_min_seconds(cfg: dict, token_steps: float, tokens: float,
                             experts_touched: float, contexts, peaks: dict,
                             bytes_per_weight: int = 2,
                             bytes_per_cache_value: int = 2) -> dict:
    """Least seconds for ``token_steps`` decode steps that served ``tokens`` tokens in all
    (``tokens / token_steps`` live rows a step), touched ``experts_touched`` (expert, layer,
    step) triples, at the contexts ``contexts`` (one entry a live row of an average step).
    Returns ``{"seconds", "bound", "bytes", "flops"}``."""
    w = weight_counts(cfg)
    n_full, n_window = layer_counts(cfg)
    L = n_full + n_window
    nh, G, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    W = cfg["sliding_window_size"]
    contexts = np.asarray(contexts, np.float64)
    if not len(contexts):           # no live row seen: the weights' stream alone
        contexts = np.zeros((1,))
    keys = n_full * contexts.mean() + n_window * np.minimum(contexts, W).mean()
    step_weights = (L * w["attention"] + w["head"]) * bytes_per_weight \
        + L * w["router"] * 4
    nbytes = token_steps * step_weights \
        + experts_touched * w["expert"] * bytes_per_weight \
        + tokens * keys * G * d * 2 * bytes_per_cache_value
    per_token = L * (w["attention"] + w["router"]
                     + cfg["moe_num_active_primary_experts"] * w["expert"]) \
        + w["head"] + keys * nh * d * 2
    flops = 2.0 * tokens * per_token
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
