"""Share of its roofline that the decode program of a decoder-hybrid-decoder reached
(``rooflines_ssm_hybrid``): the least seconds of the traced slice's decode token-steps
(weights once a step, the shared cache of a row's whole context once, a window layer's
last window of it, the Mamba state read and written; contexts from the client log) over
the summed device time of the decode program in the slice.

Nothing to read (no trace, a configuration without the keys): None."""

import rooflines_ssm_hybrid as rsh
from readers import counter_ratio
from rooflines_window_moe import live_contexts


def read(facts, program="jit_pdecode", steps="decode_steps", bytes_per_weight=2,
         bytes_per_cache_value=2, bytes_per_state_value=4, scale=100.0):
    cfg = facts.get("config") or {}
    if "mb_per_layer" not in cfg or "mamba_d_state" not in cfg:
        return None
    tr = facts.get("trace")
    pair = (facts.get("counters") or {}).get("trace")
    if not tr or not pair or pair[1] is None:
        return None
    program_s = sum(v for k, v in tr["program_s"].items() if k.startswith(program))
    token_steps = counter_ratio.delta(facts, [[steps, 1]], "trace")
    tokens = counter_ratio.delta(
        facts, [["generated_tokens", 1], ["admitted", -1]], "trace")
    if not program_s or not token_steps or tokens is None:
        return None
    least = rsh.decode_steps_min_seconds(
        cfg, token_steps, tokens, live_contexts(facts, pair[0]["t"], pair[1]["t"]),
        facts["peaks"], bytes_per_weight, bytes_per_cache_value, bytes_per_state_value)
    return scale * least["seconds"] / program_s
