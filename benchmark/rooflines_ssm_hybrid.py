"""Operations and bytes that a decode token-step of a decoder-hybrid-decoder (Mamba
layers, window and full differential attention, a cross-decoder that reads the full
layer's one cache and gates the last Mamba layer's output) needs, computed from the
configuration file's published keys and the Mamba sizes it assumes.

Kept with the benchmark (beside the other ``rooflines*.py``, which an added cell may not
edit) so that no PR that claims a gain can change how the step's share is counted.  It
counts the LEAST work, whatever implements it, so that the share cannot read over 100 %.
A decode TOKEN-STEP (one token for every live row, all layers) reads:

- every weight once (the matmul weights in the served type, the norms, lambda vectors,
  convolution, ``A_log``, ``D`` and ``b_dt`` in float32; the tied embedding once, as the
  head), however many rows it serves;
- for each live row, the full layer's K and V of its whole context ONCE: eight layers (the
  full layer and the cross layers) read that one cache, and the least any implementation
  must read is one pass, so the other seven show as the gap;
- for each live row, each window layer's K and V of its last ``min(context,
  sliding_window)`` positions;
- for each live row, each Mamba layer's ``conv`` and ``ssm`` state, read and written;

and does two FLOP a weight a token it multiplies, plus each attention read's scores and
values over the keys above (the full layer's context eight times: each reader multiplies
it).  Activations, block tables, the rows a step writes, the embedding rows and the
scan's elementwise work are left out (thousands of times smaller).
"""

from __future__ import annotations

import numpy as np


def sizes(cfg: dict) -> dict:
    H = cfg["hidden_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"H": H, "F": cfg["intermediate_size"], "Di": cfg["mamba_expand"] * H,
            "N": cfg["mamba_d_state"], "K": cfg["mamba_d_conv"], "R": cfg["mamba_dt_rank"],
            "d": H // nh, "nh": nh, "nkv": nkv, "V": cfg["vocab_size"],
            "W": cfg["sliding_window"]}


def layer_counts(cfg: dict) -> dict:
    """Layers of each kind: ``mamba``, ``window``, ``full``, ``gmu``, ``cross``."""
    n, mb = cfg["num_hidden_layers"], cfg["mb_per_layer"]
    h = n // 2
    out = {"mamba": 0, "window": 0, "full": 0, "gmu": 0, "cross": 0}
    for l in range(n):
        kind = (("mamba" if l % mb == 0 else "window") if l <= h else "full"
                if l == h + 1 else ("gmu" if l % mb == 0 else "cross"))
        out[kind] += 1
    return out


def weight_counts(cfg: dict) -> dict:
    """Numbers of weights a layer of each kind holds, split by type: ``<kind>`` the
    matmul weights (its MLP included), ``<kind>_f32`` the rest; ``head`` the tied
    embedding's."""
    s = sizes(cfg)
    H, F, Di, N, K, R, d = s["H"], s["F"], s["Di"], s["N"], s["K"], s["R"], s["d"]
    mlp, ln = 3 * H * F, 4 * H
    diff = 4 * d + 2 * d
    nkv = s["nkv"] * d
    return {"mamba": mlp + H * 2 * Di + Di * (R + 2 * N) + R * Di + Di * H,
            "mamba_f32": ln + K * Di + Di + Di + Di * N + Di,
            "attention": mlp + H * (H + 2 * nkv) + H * H, "attention_f32": ln + diff,
            "gmu": mlp + 2 * H * Di, "gmu_f32": ln,
            "cross": mlp + 2 * H * H, "cross_f32": ln + diff,
            "head": s["V"] * H, "final_f32": 2 * H}


def decode_steps_min_seconds(cfg: dict, token_steps: float, tokens: float, contexts,
                             peaks: dict, bytes_per_weight: int = 2,
                             bytes_per_cache_value: int = 2,
                             bytes_per_state_value: int = 4) -> dict:
    """Least seconds for ``token_steps`` decode steps that served ``tokens`` tokens in all
    (``tokens / token_steps`` live rows a step), at the contexts ``contexts`` (one entry a
    live row of an average step).  Returns ``{"seconds", "bound", "bytes", "flops"}``."""
    s, n, w = sizes(cfg), layer_counts(cfg), weight_counts(cfg)
    attn = n["window"] + n["full"]
    matmul = n["mamba"] * w["mamba"] + attn * w["attention"] + n["gmu"] * w["gmu"] \
        + n["cross"] * w["cross"] + w["head"]
    f32 = n["mamba"] * w["mamba_f32"] + attn * w["attention_f32"] \
        + n["gmu"] * w["gmu_f32"] + n["cross"] * w["cross_f32"] + w["final_f32"]
    contexts = np.asarray(contexts, np.float64)
    if not len(contexts):           # no live row seen: the weights' stream alone
        contexts = np.zeros((1,))
    full, window = contexts.mean(), np.minimum(contexts, s["W"]).mean()
    kv_width = s["nkv"] * s["d"]                    # K (and V) values a position
    state = n["mamba"] * (s["K"] - 1 + s["N"]) * s["Di"]
    nbytes = token_steps * (matmul * bytes_per_weight + f32 * 4) \
        + tokens * ((full + n["window"] * window) * kv_width * 2 * bytes_per_cache_value
                    + 2 * state * bytes_per_state_value)
    # scores over d-wide halves and values over 2d-wide pairs, two halves a head
    per_key = s["nh"] * s["d"] * 2 + s["nh"] * 2 * s["d"] * 2
    flops = 2.0 * tokens * matmul + tokens * per_key * (
        (n["full"] + n["cross"]) * full + n["window"] * window)
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes), "bytes": nbytes, "flops": flops,
            "bound": "memory" if by_bytes >= by_flops else "compute"}
