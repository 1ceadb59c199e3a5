"""Work completed per second per chip, from the runner's completion stamps
``facts["work"] = [(t, items)]`` (host clock at the system's output).

``span="window"``: every item stamped inside the window over the window's length.
``span="stamps"``: for work that completes in lumps (a training step), the items
stamped after the window's first stamp up to its last, over the time between those
two stamps, so that a lump cut by the window's edge does not quantise the rate.
With no ``span`` argument the runner's ``facts["rate_span"]`` decides (``window``
if it gives none)."""


def read(facts, span=None):
    span = span or facts.get("rate_span", "window")
    t0, t1 = facts["window"]
    inside = sorted((t, n) for t, n in facts["work"] if t0 <= t < t1)
    if span == "window":
        items, seconds = sum(n for _, n in inside), t1 - t0
    else:
        if len(inside) < 2:
            return None
        items = sum(n for _, n in inside[1:])
        seconds = inside[-1][0] - inside[0][0]
    if seconds <= 0 or items <= 0:
        return None
    return items / seconds / facts["chips"]
