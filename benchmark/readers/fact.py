"""A number the runner already holds (``facts[key]``), optionally as a share of one
of the device's peaks (``peaks.json``)."""


def read(facts, key, over_peak=None, scale=1.0):
    value = facts.get(key)
    if value is None:
        return None
    if over_peak is not None:
        value = value / facts["peaks"][over_peak]
    return scale * value
