"""A ratio of deltas of the program's own counters between two snapshots the runner
took (``facts["counters"][over] = [start, end]``; ``over`` is ``window`` or
``trace``).  ``num`` and ``den`` are lists of ``[counter, weight]``; ``den_times``
names a counter whose END value multiplies the denominator (a number of slots)."""


def delta(facts, terms, over):
    pair = (facts.get("counters") or {}).get(over)
    if not pair or pair[0] is None or pair[1] is None:
        return None
    start, end = pair
    if any(name not in end for name, _ in terms):
        return None
    return sum(w * (end[name] - start[name]) for name, w in terms)


def read(facts, num, den, over="window", den_times=None, scale=1.0):
    n, d = delta(facts, num, over), delta(facts, den, over)
    if n is None or not d:
        return None
    if den_times is not None:
        d *= facts["counters"][over][1][den_times]
    return scale * n / d
