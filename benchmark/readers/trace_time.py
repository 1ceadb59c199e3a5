"""Device time from the reduced profiler trace (``xplane.reduce``): the summed time
of the programs and operations whose names start with one of ``programs`` / ``ops``
(none given: the busy union), averaged over the chips, then

- ``per="window"``: over the traced window,
- ``per="busy"``: over the busy union,
- ``per=<counter>``: over that counter's delta across the traced window (the
  program's own count of the work done, e.g. token-level decode steps),
times ``scale``; ``complement`` gives ``scale * (1 - share)`` (an idle share)."""

from readers import counter_ratio


def read(facts, programs=(), ops=(), per="window", scale=1.0,
         complement=False):
    tr = facts.get("trace")
    if not tr:
        return None
    if programs or ops:
        seconds = sum(v for k, v in tr["program_s"].items()
                      if any(k.startswith(p) for p in programs)) \
            + sum(v for k, v in tr["op_s"].items()
                  if any(k.startswith(p) for p in ops))
    else:
        seconds = tr["busy_s"]
    if per == "window":
        base = tr["window_s"]
    elif per == "busy":
        base = tr["busy_s"]
    else:
        base = counter_ratio.delta(facts, [[per, 1]], "trace")
    if not base:
        return None
    share = seconds / base
    return scale * (1.0 - share if complement else share)
