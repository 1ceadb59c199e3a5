"""Share of its roofline that the decode step of a latent-attention, routed-expert
decoder reached in the traced window: the least seconds its token-steps needed
(``rooflines_lm.decode_steps_min_seconds``: weights once a step, the routed experts
the program counted as touched, the indexer's keys of the live context, the selected
latent rows) over the summed device time of the decode program.  Nothing to read (no
trace, a program without the counters, a configuration without the keys): None."""

import rooflines_lm
from readers import counter_ratio


def read(facts, program="jit_pdecode", steps="decode_steps",
         touched="model.moe_experts_touched", bytes_per_weight=2,
         bytes_per_cache_value=2, scale=100.0):
    tr = facts.get("trace")
    pair = (facts.get("counters") or {}).get("trace")
    cfg = facts.get("config") or {}
    if not tr or not pair or pair[1] is None or "index_topk" not in cfg:
        return None
    program_s = sum(v for k, v in tr["program_s"].items()
                    if k.startswith(program))
    token_steps = counter_ratio.delta(facts, [[steps, 1]], "trace")
    experts = counter_ratio.delta(facts, [[touched, 1]], "trace")
    tokens = counter_ratio.delta(
        facts, [["generated_tokens", 1], ["admitted", -1]], "trace")
    if not program_s or not token_steps or experts is None or tokens is None:
        return None
    lo, hi = pair[0]["t"], pair[1]["t"]
    least = rooflines_lm.decode_steps_min_seconds(
        cfg, token_steps, tokens, experts,
        rooflines_lm.live_context(facts, lo, hi),
        rooflines_lm.live_context(facts, lo, hi, cap=cfg["index_topk"]),
        facts["peaks"], bytes_per_weight, bytes_per_cache_value)
    return scale * least["seconds"] / program_s
