"""Share of its roofline that the decode program of a linear + block-sparse decoder
reached (``rooflines_sparse_linear``): the least seconds of the traced slice's decode
token-steps (weights once a step, the recurrent state read and written once a live row a
linear layer, the live contexts' compressed keys and kept blocks; contexts from the
client log) over the summed device time of the decode program in the slice.

The prefill program has no such reader yet: a 4 s slice holds two or three prefill calls
of 1-2 s, ``xplane.reduce`` hands readers each program's CLIPPED time summed, and whole
calls cannot be paired with their own device time from that (PERF.md section 7).

Nothing to read (no trace, a program without the counters, a configuration without the
keys): None."""

import rooflines_sparse_linear as rsl
from readers import counter_ratio


def read(facts, program, steps="decode_steps", bytes_per_weight=2,
         bytes_per_cache_value=2, scale=100.0):
    cfg = facts.get("config") or {}
    if "mixer_types" not in cfg or "sparse_config" not in cfg:
        return None
    tr = facts.get("trace")
    pair = (facts.get("counters") or {}).get("trace")
    if not tr or not pair or pair[1] is None:
        return None
    program_s = sum(v for k, v in tr["program_s"].items()
                    if k.startswith(program))
    token_steps = counter_ratio.delta(facts, [[steps, 1]], "trace")
    tokens = counter_ratio.delta(
        facts, [["generated_tokens", 1], ["admitted", -1]], "trace")
    if not program_s or not token_steps or tokens is None:
        return None
    least = rsl.decode_steps_min_seconds(
        cfg, token_steps, tokens,
        rsl.live_contexts(facts, pair[0]["t"], pair[1]["t"]), facts["peaks"],
        bytes_per_weight, bytes_per_cache_value)
    return scale * least["seconds"] / program_s
