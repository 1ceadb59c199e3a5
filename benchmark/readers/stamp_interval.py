"""Mean time between the runner's completion stamps inside the window, per
``facts["steps_per_stamp"]`` steps, in ms: the step time of a training cell, taken
from a ``block_until_ready`` the runner makes itself (the program's own
``fit_step_seconds`` times the dispatch, not the step)."""


def read(facts, scale=1e3):
    t0, t1 = facts["window"]
    inside = sorted(t for t, _ in facts["work"] if t0 <= t < t1)
    if len(inside) < 2:
        return None
    steps = (len(inside) - 1) * facts.get("steps_per_stamp", 1)
    return scale * (inside[-1] - inside[0]) / steps
