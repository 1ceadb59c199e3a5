"""What the program's own counters said when the window OPENED: the START snapshot
of ``facts["counters"]["window"]`` (``ContinuousBatcher.stats()`` as the runner
flattened it, taken right after the window's first instant).  Either one ``key``'s
value, or ``until - since`` of two instants on the host's monotonic clock: a key of
the snapshot that holds one, or ``"process"`` (the benchmark's own process origin,
``window[0] - setup_seconds``) or ``"window"`` (``window[0]``).  A missing key, as on
a program that does not publish it, reads None."""


def instant(facts, start, name):
    window = facts.get("window")
    if name == "window":
        return window[0] if window else None
    if name == "process":
        seconds = facts.get("setup_seconds")
        if not window or seconds is None:
            return None
        return window[0] - seconds
    return start.get(name)


def read(facts, key=None, since=None, until=None, scale=1.0):
    pair = (facts.get("counters") or {}).get("window")
    if not pair or pair[0] is None:
        return None
    start = pair[0]
    if key is not None:
        value = start.get(key)
    else:
        a, b = instant(facts, start, since), instant(facts, start, until)
        value = None if a is None or b is None else b - a
    return None if value is None else scale * value
