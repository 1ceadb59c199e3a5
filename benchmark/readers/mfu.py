"""Model FLOP/s utilisation of a training cell: samples per second per chip (the
``rate`` reader) times the FLOPs the forward and backward passes need per sample
(``rooflines.<flops_fn>(1)``; nothing recomputed is counted) over the chip's bf16
peak, in %."""

import rooflines
from readers import rate


def read(facts, flops_fn, span="stamps", scale=100.0):
    per_chip = rate.read(facts, span=span)
    if per_chip is None:
        return None
    flops = getattr(rooflines, flops_fn)(1)
    return scale * per_chip * flops / facts["peaks"]["bf16_flops_per_s"]
