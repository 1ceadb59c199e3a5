#!/usr/bin/env python3
"""Writes a traffic file's TABLES from a distribution, once, when a cell is defined.

The benchmark never draws from a distribution at run time: ``loadgen`` reads the
tables this prints.  Quantiles are taken at ``(i + 0.5) / n``, so a table is the
distribution's shape without sampling noise; the pairing of prompt and answer
lengths is a fixed shuffle.

    python3 benchmark/make_traffic.py lognormal 216 96 0.85 16 512  64 0.55 16 128
    python3 benchmark/make_traffic.py uniform   32  512 896  64 128
"""

import json
import math
import sys
from statistics import NormalDist

import numpy as np


def lognormal(n, median, sigma, lo, hi):
    nd = NormalDist()
    return [int(min(hi, max(lo, round(median * math.exp(
        sigma * nd.inv_cdf((i + 0.5) / n)))))) for i in range(n)]


def uniform(n, lo, hi):
    return [int(round(lo + (hi - lo) * i / (n - 1))) for i in range(n)]


def exponential_unit(n):
    g = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    mean = sum(g) / n
    return [round(x / mean, 6) for x in g]


def main(argv):
    kind, n = argv[0], int(argv[1])
    a = [float(x) for x in argv[2:]]
    if kind == "lognormal":
        prompts, answers = lognormal(n, *a[0:4]), lognormal(n, *a[4:8])
    else:
        prompts, answers = uniform(n, *a[0:2]), uniform(n, *a[2:4])
    answers = [answers[i] for i in np.random.default_rng(0).permutation(n)]
    print(json.dumps({"pairs": [list(p) for p in zip(prompts, answers)],
                      "gaps_unit": exponential_unit(n)}))


if __name__ == "__main__":
    main(sys.argv[1:])
