"""Share of its memory roofline that the paged decode-attention kernel reached in
the traced window: (bytes the token-steps had to read / peak HBM bytes/s) over the
summed device time of the kernel's events.  Memory-bound (``rooflines.py``).

Live context tokens are taken from the client log (prompt length + tokens streamed
so far of every request between its first and last stamp), averaged over the traced
window; the pool's ``used_blocks`` would overcount, because blocks are reserved for
a request's whole answer at admission."""

import numpy as np

import rooflines
from readers import counter_ratio


def live_tokens(facts, lo, hi, points=200):
    total = 0.0
    for t in np.linspace(lo, hi, points):
        for r in facts["requests"]:
            st = r["stamps"]
            if not st or not (st[0][0] <= t < st[-1][0]):
                continue
            n = max(n for ts, n in st if ts <= t)
            total += r["prompt_len"] + n
    return total / points


def read(facts, kernel="paged_attention", steps="decode_steps",
         bytes_per_value=4, scale=100.0):
    tr = facts.get("trace")
    pair = (facts.get("counters") or {}).get("trace")
    if not tr or not pair or pair[1] is None:
        return None
    kernel_s = sum(v for k, v in tr["op_s"].items() if k.startswith(kernel))
    token_steps = counter_ratio.delta(facts, [[steps, 1]], "trace")
    if not kernel_s or not token_steps:
        return None
    model = facts["config"]["model"]
    least = rooflines.paged_attention_min_seconds(
        live_tokens(facts, pair[0]["t"], pair[1]["t"]), token_steps,
        model["n_layer"], model["n_embd"], bytes_per_value,
        facts["peaks"]["hbm_bytes_per_s"])
    return scale * least / kernel_s
