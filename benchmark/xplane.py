"""Reduction of a jax profiler trace (``*.xplane.pb``) to what the readers use.

Read with ``jax.profiler.ProfileData`` alone.  On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Modules`` holds one event per program run
(``jit_pdecode(<fingerprint>)``) and whose line ``XLA Ops`` holds the operations
inside them (``%paged_attention.228 = f32[...] custom-call(...)``); host threads
are lines of the plane ``/host:CPU``, and ``jax.profiler.TraceAnnotation`` spans
appear there under their own names.  All planes share one clock.

The traced window is the span named ``WINDOW_SPAN`` that the runner holds open
between ``start_trace`` and ``stop_trace``; device events are clipped to it.

- busy: the UNION of the ``XLA Ops`` intervals of a chip (a ``while`` event
  covers its body's events, so a sum would count them twice), averaged over chips.
- an operation's time: the sum of its events, by name without the ``.<n>`` suffix,
  averaged over chips; control-flow containers (``CONTAINERS``) are left out of
  the top list because their time is that of their bodies.
- idle gaps: the longest gaps of chip 0's busy union, each named by the
  ``bench.*`` host span open at its middle (innermost first) and by the device
  operations on either side.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.traced_window"
TRACE_AFTER_S, TRACE_SECONDS = 2.0, 4.0     # a traced run traces this slice of its window
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, PROGRAMS_LINE = "XLA Ops", "XLA Modules"
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[float, float]      # start_s, end_s


def find_trace_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


@functools.lru_cache(maxsize=None)     # a trace repeats a few thousand names
def op_name(event_name: str) -> str:
    """``%paged_attention.228 = f32[...] custom-call(...)`` -> ``paged_attention``;
    ``jit_pdecode(1833...)`` -> ``jit_pdecode``."""
    name = event_name.split(" = ", 1)[0].strip().lstrip("%")
    name = name.split("(", 1)[0]
    return re.sub(r"\.\d+$", "", name)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def load(path: str) -> dict:
    """``{"devices": {n: {"ops": [(name, s, e)], "programs": [...]}},
    "spans": [(name, s, e)]}`` with times in seconds on the trace's clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, dict] = {}
    spans = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "programs": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", PROGRAMS_LINE: "programs"} \
                    .get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    dev[key].append((op_name(ev.name), s,
                                     s + ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return {"devices": devices, "spans": spans}


def reduce(loaded: dict, chips: int) -> dict:
    """The document the readers see under ``facts["trace"]``; None when the trace
    holds no ``/device:TPU`` plane."""
    windows = [(s, e) for name, s, e in loaded["spans"] if name == WINDOW_SPAN]
    devices = [loaded["devices"][k] for k in sorted(loaded["devices"])][:chips]
    if not devices:
        return None                 # no chip was traced: nothing to read
    if windows:
        lo, hi = windows[0]
    else:                           # no marker: the extent of the device events
        every = [iv[1:] for d in devices for iv in d["ops"]]
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
    window_s = hi - lo
    n = len(devices)
    busy_s = 0.0
    op_s: Dict[str, float] = {}
    program_s: Dict[str, float] = {}
    program_n: Dict[str, float] = {}
    for dev in devices:
        busy_s += sum(e - s for s, e in union(clip(
            [(s, e) for _, s, e in dev["ops"]], lo, hi))) / n
        for name, s, e in dev["ops"]:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_s[name] = op_s.get(name, 0.0) + d / n
        for name, s, e in dev["programs"]:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                program_s[name] = program_s.get(name, 0.0) + d / n
                program_n[name] = program_n.get(name, 0.0) + 1.0 / n
    top = [["program:" + k, v] for k, v in program_s.items()] \
        + [[k, v] for k, v in op_s.items() if k not in CONTAINERS]
    top.sort(key=lambda kv: -kv[1])
    return {"window_s": window_s, "busy_s": busy_s, "chips": n,
            "op_s": op_s, "program_s": program_s, "program_n": program_n,
            "device_ops": top[:10],
            "idle_gaps": idle_gaps(devices[0], loaded["spans"], lo, hi)}


def idle_gaps(dev: dict, spans: list, lo: float, hi: float,
              keep: int = 10) -> list:
    busy = union(clip([(s, e) for _, s, e in dev["ops"]], lo, hi))
    ops = sorted(((s, e, name) for name, s, e in dev["ops"]
                  if e > lo and s < hi and name not in CONTAINERS))
    edges = [(lo, lo)] + busy + [(hi, hi)]
    gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(edges, edges[1:])
            if b[0] > a[1]]
    gaps.sort(reverse=True)
    host = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    starts = [o[0] for o in ops]
    by_end = sorted((o[1], o[2]) for o in ops)
    ends = [o[0] for o in by_end]
    out = []
    for length, s, e in gaps[:keep]:
        mid = (s + e) / 2
        inside = [sp for sp in host if sp[1] <= mid < sp[2]]
        span = min(inside, key=lambda sp: sp[2] - sp[1])[0] if inside \
            else "host:unannotated"
        i = bisect.bisect_right(ends, s + 1e-9) - 1
        j = bisect.bisect_left(starts, e - 1e-9)
        before = by_end[i][1] if i >= 0 else "start"
        after = ops[j][2] if j < len(ops) else "end"
        out.append([f"{span}|{before}->{after}", length])
    return out


def reduce_dir(trace_dir: str, chips: int) -> dict:
    return reduce(load(find_trace_file(trace_dir)), chips)
