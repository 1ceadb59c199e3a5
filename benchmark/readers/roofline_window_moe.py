"""Share of its roofline that the decode program of a window + full attention decoder
with routed experts reached (``rooflines_window_moe``): the least seconds of the traced
slice's decode token-steps (weights once a step, the experts the program counted as
touched, a full layer's whole live context and a window layer's last window of it; contexts
from the client log) over the summed device time of the decode program in the slice.

Nothing to read (no trace, a program without the counters, a configuration without the
keys): None."""

import rooflines_window_moe as rwm
from readers import counter_ratio


def read(facts, program="jit_pdecode", steps="decode_steps",
         touched="model.moe_experts_touched", bytes_per_weight=2,
         bytes_per_cache_value=2, scale=100.0):
    cfg = facts.get("config") or {}
    if "sliding_window_layout" not in cfg or "moe_num_primary_experts" not in cfg:
        return None
    tr = facts.get("trace")
    pair = (facts.get("counters") or {}).get("trace")
    if not tr or not pair or pair[1] is None:
        return None
    program_s = sum(v for k, v in tr["program_s"].items() if k.startswith(program))
    token_steps = counter_ratio.delta(facts, [[steps, 1]], "trace")
    experts = counter_ratio.delta(facts, [[touched, 1]], "trace")
    tokens = counter_ratio.delta(
        facts, [["generated_tokens", 1], ["admitted", -1]], "trace")
    if not program_s or not token_steps or experts is None or tokens is None:
        return None
    least = rwm.decode_steps_min_seconds(
        cfg, token_steps, tokens, experts,
        rwm.live_contexts(facts, pair[0]["t"], pair[1]["t"]), facts["peaks"],
        bytes_per_weight, bytes_per_cache_value)
    return scale * least["seconds"] / program_s
