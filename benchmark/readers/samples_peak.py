"""The peak of ``num / den`` over the counter samples the runner polled during the
window (at most 4 Hz)."""


def read(facts, num, den, scale=1.0):
    t0, t1 = facts["window"]
    values = [s[num] / s[den] for s in facts.get("samples", ())
              if t0 <= s["t"] <= t1 and s.get(den)]
    return scale * max(values) if values else None
