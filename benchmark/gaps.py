#!/usr/bin/env python3
"""Which phase of the generate loop each idle gap of the device lies in: a tool of
a cell's author, like ``sweep.py`` (no metric reads it).

    python3 benchmark/gaps.py --workload <cell> [--seed n] [--seconds 12] [--out f]
    python3 benchmark/gaps.py --workload <cell> --plain 1      (no trace: the clock alone)
    python3 benchmark/gaps.py --xplane <file.xplane.pb>

The program's generate thread opens one profiler span per phase of its cycle
(``analytics_zoo_tpu/common/observability.PhaseClock``, names ``zoo.gen.<phase>``);
``xplane.py`` keeps only ``bench.*`` host spans, so ``breakdown.idle_gaps`` cannot
name them.  This reads the same traced slice with ``jax.profiler.ProfileData``:
every gap of chip 0's busy union inside the traced window is cut along the phase
spans, so the table's seconds sum to the slice's idle time, and each of the twenty
longest gaps is named by the phase open at its middle and split by phase
(``ms_by_phase``; ``at_s`` is its start in the window).  With ``--workload`` it runs
the cell as ``run.py --trace 1`` does (one set-up, one window) and reads the trace
before the runner deletes it; beside the table it prints the program's own
``phase_s.*`` over the slice, which must agree with the spans.  An active trace
slows the host's phases themselves, so ``--plain 1`` runs the window without one and
prints only the program's clock (and the links of the time to first token) over it.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
import time

T_PROCESS = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import run as bench                                    # noqa: E402
import xplane                                          # noqa: E402

PHASE_PREFIX = "zoo.gen."
OUTSIDE = "no_phase_span"
LONGEST = 20


def load(path: str):
    """``(busy, ops, phases, (lo, hi))``: chip 0's busy union and operations
    clipped to the traced window, and the phase spans ``(start, end, phase)``.  A
    trace the benchmark did not take (``manager profile``) has no window span:
    the window is then from the device's first operation to its last."""
    from jax.profiler import ProfileData
    loaded = xplane.load(path)
    dev = loaded["devices"][min(loaded["devices"])]
    lo, hi = next(((s, e) for name, s, e in loaded["spans"]
                   if name == xplane.WINDOW_SPAN),
                  (min(s for _, s, _ in dev["ops"]),
                   max(e for _, _, e in dev["ops"])))
    busy = xplane.union(xplane.clip([(s, e) for _, s, e in dev["ops"]], lo, hi))
    ops = sorted((s, e, name) for name, s, e in dev["ops"]
                 if e > lo and s < hi and name not in xplane.CONTAINERS)
    phases = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PHASE_PREFIX):
                    s = ev.start_ns * 1e-9
                    phases.append((s, s + ev.duration_ns * 1e-9,
                                   ev.name[len(PHASE_PREFIX):]))
    return busy, ops, sorted(phases), (lo, hi)


def attribute(busy, ops, phases, window) -> dict:
    lo, hi = window
    edges = [(lo, lo)] + busy + [(hi, hi)]
    gaps = [(a[1], b[0]) for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
    starts = [p[0] for p in phases]
    op_starts = [o[0] for o in ops]
    by_end = sorted((o[1], o[2]) for o in ops)
    op_ends = [o[0] for o in by_end]

    def overlapping(s, e):
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(phases) and phases[i][0] < e:
            if phases[i][1] > s:
                yield phases[i]
            i += 1

    idle_by_phase, span_by_phase = {}, {}
    for s, e, name in phases:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            span_by_phase[name] = span_by_phase.get(name, 0.0) + d
    longest = []
    for s, e in gaps:
        mine = {}                      # this gap's seconds by phase
        for ps, pe, name in overlapping(s, e):
            mine[name] = mine.get(name, 0.0) + min(e, pe) - max(s, ps)
        if e - s - sum(mine.values()) > 1e-9:
            mine[OUTSIDE] = e - s - sum(mine.values())
        for name, d in mine.items():
            idle_by_phase[name] = idle_by_phase.get(name, 0.0) + d
        mid = (s + e) / 2
        at_mid = [name for ps, pe, name in overlapping(s, e) if ps <= mid < pe]
        longest.append((s, e, at_mid[0] if at_mid else OUTSIDE, mine))
    longest.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e, at_mid, mine in longest[:LONGEST]:
        i = bisect.bisect_right(op_ends, s + 1e-9) - 1
        j = bisect.bisect_left(op_starts, e - 1e-9)
        named.append({"ms": 1e3 * (e - s), "at_s": s - lo,
                      "phase_at_middle": at_mid,
                      "ms_by_phase": {k: round(1e3 * v, 3)
                                      for k, v in mine.items()},
                      "before": by_end[i][1] if i >= 0 else "start",
                      "after": ops[j][2] if j < len(ops) else "end"})
    busy_s = sum(e - s for s, e in busy)
    return {"window_s": hi - lo, "busy_s": busy_s,
            "idle_share": 1 - busy_s / (hi - lo), "gaps": len(gaps),
            "idle_s_by_phase": dict(sorted(idle_by_phase.items(),
                                           key=lambda kv: -kv[1])),
            "span_s_by_phase": span_by_phase, "longest": named}


CHAIN = ("intake", "queue_wait", "prefill", "first_out")


def counters_over(counters: dict, over: str) -> dict:
    """The program's own clock over the traced slice or the whole window:
    ``phase_s.*``, ``phase_n.*``, ``loop_s``, ``boundaries`` and the TTFT chain's
    sums and counts, as deltas of the runner's two snapshots."""
    start, end = counters[over]
    keep = {"loop_s", "boundaries"} | {c + "_s_sum" for c in CHAIN} \
        | {c + "_n" for c in CHAIN}
    return {k: end[k] - start[k] for k in end
            if k.startswith(("phase_s.", "phase_n.")) or k in keep}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--xplane")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--plain", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args()
    doc = {}
    if args.xplane:
        doc = attribute(*load(args.xplane))
    else:
        _, cell, config, traffic = bench.load_cell(bench.ROOT, args.workload)
        device = bench.device_or_refuse(cell["chips"])
        from analytics_zoo_tpu.inference import aot
        aot.enable_persistent_cache()
        from runners import serve_generate
        reduce_dir = xplane.reduce_dir

        def read_then_reduce(trace_dir, chips):     # the runner deletes the trace
            doc.update(attribute(*load(xplane.find_trace_file(trace_dir))))
            return reduce_dir(trace_dir, chips)
        xplane.reduce_dir = read_then_reduce
        session = serve_generate.Session({
            "config": config, "traffic": traffic, "chips": cell["chips"],
            "seed": args.seed, "trace": not args.plain, "peaks": device["peaks"],
            "t_process": T_PROCESS}).start()
        try:
            m = session.measure(args.seconds)
        finally:
            session.close()
            xplane.reduce_dir = reduce_dir
        doc["cell"] = args.workload
        doc["program_clock"] = counters_over(
            m["counters"], "window" if args.plain else "trace")
        doc["failed"] = m["failed"]
    print(json.dumps(doc, indent=1), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
