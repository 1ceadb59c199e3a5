"""What a client of the served model sees, from the stamps taken at the engine's
output queue, over the window's finished requests (``in_window`` by due time).

- ``what="ttft"``: first streamed partial seen - the time the request was DUE
  (so a stalled generator or a queue counts against the system), in ms.
- ``what="tpot"``: (last token time - first partial time) / (tokens after the
  first partial), in ms.
- ``what="late"``: sent - due, how late the load generator ran, in ms.
``stat`` is ``mean`` or ``p<q>`` (numpy's linear-interpolated percentile)."""

import numpy as np


def sample(facts, what):
    out = []
    for r in facts["requests"]:
        if not (r["in_window"] and r["ok"] and r["stamps"]):
            continue
        (t_first, n_first), (t_last, n_last) = r["stamps"][0], r["stamps"][-1]
        if what == "ttft":
            out.append(1e3 * (t_first - r["due"]))
        elif what == "late":
            out.append(1e3 * (r["sent"] - r["due"]))
        elif what == "tpot":
            if n_last > n_first:
                out.append(1e3 * (t_last - t_first) / (n_last - n_first))
        else:
            raise ValueError(f"unknown what={what!r}")
    return out


def read(facts, what, stat):
    values = sample(facts, what)
    if not values:
        return None
    if stat == "mean":
        return float(np.mean(values))
    return float(np.percentile(values, float(stat[1:])))
