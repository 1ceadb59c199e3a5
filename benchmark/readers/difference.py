"""``a - b`` of two other readers' values: ``{"reader": ..., "args": {...}}`` each."""

import importlib


def read(facts, a, b):
    values = [importlib.import_module("readers." + side["reader"])
              .read(facts, **side.get("args", {})) for side in (a, b)]
    if None in values:
        return None
    return values[0] - values[1]
