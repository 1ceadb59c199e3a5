"""Unified telemetry — metrics registry, Prometheus exposition, tracing.

PR 1-3 each grew a bespoke signal surface: `/metrics` served a hand-rolled
JSON dict, `StageStats` reservoirs lived only inside the serving engine, and
`Estimator.fit` measured itself with raw `time.time()`.  This module is the
one telemetry layer all of them now share (the Prometheus/Borgmon pull-
metrics + Dapper per-request-trace shape):

- ``MetricsRegistry`` — process- or component-scoped registry of labeled
  ``Counter`` / ``Gauge`` / ``Histogram`` primitives.  Thread-safe (the
  serving workers record from three threads; training from the fit loop).
  Histograms keep cumulative bucket counts for Prometheus exposition AND a
  bounded reservoir of recent samples for p50/p95/p99 summaries — subsuming
  what the engine's ``StageStats`` did.
- ``MetricsRegistry.to_prometheus()`` — text exposition format v0.0.4
  (``# HELP`` / ``# TYPE`` / ``name{label="v"} value`` with
  ``_bucket``/``_sum``/``_count`` histogram series), served by
  ``serving/http.py`` under ``/metrics?format=prom``.
- ``Tracer`` — per-record spans in a bounded ring buffer.  A ``trace_id``
  is stamped on each record at client enqueue (riding the wire next to
  ``deadline_ns``); the engine records one span per pipeline stage per
  record (read → preprocess → stage_wait → predict → write), with the error
  attached for quarantined/shed records, and can export Chrome trace-event
  JSON for Perfetto / ``chrome://tracing`` (``tools/trace_view.py``
  summarizes a dump offline).

Fleet-wide distributed tracing (PR 13, the Dapper shape): spans now carry
``span_id``/``parent_id``/``replica_id``, and a ``SpanContext`` serializes
to a W3C-style ``traceparent`` string (``00-<trace>-<span>-<flags>``) so a
trace CROSSES process boundaries — the LB opens the root span and forwards
the header, the gateway continues it and stamps the context onto the wire
frame, and every engine stage span parents under it.  Head sampling is a
pure function of the trace_id (``trace_sampled``) so every process in the
fleet reaches the same verdict without coordination; error spans are
always recorded AND kept in a small separate bounded buffer so a burst of
per-boundary decode spans cannot evict the one quarantine span being
diagnosed.  ``Tracer.drain_spans()`` is the export hop the per-replica
spool writers use (``serving/tracecollect.py`` merges spools fleet-wide).

``SloTracker`` attributes each latency-objective violation to its dominant
pipeline stage (``serving_slo_violations_total{stage=}``) and maintains a
windowed burn-rate gauge, feeding the fleet metrics merge.

Incident forensics (PR 15): ``FlightRecorder`` is the black-box half the
trace spans never carried — a bounded, lock-cheap ring of typed EVENTS
(state transitions, retunes, reclaims, quarantines, warm-up phases,
compile requests, scheduler boundaries, autoscaler decisions) that every
subsystem already emitting a log line also records.  Events live on the
monotonic clock like spans and drain through the same spool contract
(``serving/tracecollect.append_events`` / ``merge_spools``), so `manager
incident` snapshots one merged cross-process timeline of what every
process was DOING around a crash or SLO burn, not just where time went.
``process_stats()`` is the per-process resource read (RSS, CPU seconds,
open FDs, thread count) the health doc and prom exposition carry.

``PhaseClock`` (PR 25) partitions one thread's wall time into named phases
(the generate loop's cycle: idle / intake / admit / dispatch / decode_wait
/ fold / ...): cumulative seconds per phase for ``/healthz`` and the
registry, and one profiler annotation per phase so a device trace shows
what the host was doing in every gap.

Pure stdlib + numpy-free: safe to import from the client, the queues, and
the trainer without dragging in jax.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import threading
import time
import uuid
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

# Latency-in-seconds default, sub-ms to 10 s — covers queue polls through
# cold predict compiles.
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


def _escape_label(v: str) -> str:
    return (str(v).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _escape_help(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace("\n", "\\n")


def _fmt(v: float) -> str:
    """Prometheus sample-value formatting: integers render bare (``3``),
    floats via repr (``0.005``), specials as ``+Inf``/``-Inf``/``NaN``."""
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if math.isnan(v):
        return "NaN"
    if float(v) == int(v):
        return str(int(v))
    return repr(float(v))


def _percentile(sorted_vals: List[float], q: float) -> float:
    """numpy.percentile(interpolation='linear') over an already-sorted list —
    keeps this module numpy-free while matching the StageStats numbers."""
    n = len(sorted_vals)
    if n == 1:
        return sorted_vals[0]
    pos = (q / 100.0) * (n - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, n - 1)
    frac = pos - lo
    return sorted_vals[lo] * (1.0 - frac) + sorted_vals[hi] * frac


class _Metric:
    """Base labeled metric: children are keyed by their label-value tuple;
    an unlabeled metric uses its single ``()`` child, reachable through the
    convenience methods on the metric itself."""

    kind = "untyped"

    def __init__(self, name: str, help: str = "",
                 labelnames: Sequence[str] = ()):
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._children: Dict[Tuple[str, ...], object] = {}
        self._lock = threading.Lock()

    def _make_child(self):
        raise NotImplementedError

    def _resolve_key(self, values, kv) -> Tuple[str, ...]:
        if kv:
            if values:
                raise ValueError("pass label values positionally OR by name")
            unexpected = set(kv) - set(self.labelnames)
            if unexpected:
                raise ValueError(
                    f"{self.name}: unexpected label(s) {sorted(unexpected)} "
                    f"(expected {self.labelnames})")
            try:
                values = tuple(kv[ln] for ln in self.labelnames)
            except KeyError as e:
                raise ValueError(
                    f"{self.name}: missing label {e} "
                    f"(expected {self.labelnames})") from e
        if len(values) != len(self.labelnames):
            raise ValueError(
                f"{self.name}: got {len(values)} label values, "
                f"expected {len(self.labelnames)}")
        return tuple(str(v) for v in values)

    def labels(self, *values, **kv):
        key = self._resolve_key(values, kv)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._children[key] = self._make_child()
            return child

    def remove(self, *values, **kv) -> None:
        """Drop one labeled child series entirely (PR 5 scale-down): a
        removed replica's per-replica series must DISAPPEAR from the
        exposition and snapshots, not linger with a stale or zero value.
        No-op when the child was never created."""
        key = self._resolve_key(values, kv)
        with self._lock:
            self._children.pop(key, None)

    def bare(self):
        """The unlabeled ``()`` child of a LABELED metric.  It renders
        without braces — legal in the text exposition, where a family may
        carry an aggregate sample next to its labeled series — so a metric
        can keep its historical unlabeled sample while growing labeled
        dimensions (PR 19: ``serving_slo_burn_rate`` stays the fleet-global
        bare sample, ``serving_slo_burn_rate{tenant=...}`` are the
        per-tenant views)."""
        with self._lock:
            child = self._children.get(())
            if child is None:
                child = self._children[()] = self._make_child()
            return child

    def _default(self):
        if self.labelnames:
            raise ValueError(
                f"{self.name} is labeled {self.labelnames}: "
                "call .labels(...) first")
        return self.labels()

    def children(self) -> List[Tuple[Tuple[str, ...], object]]:
        with self._lock:
            return list(self._children.items())


class _CounterChild:
    __slots__ = ("_value", "_lock")

    def __init__(self):
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        if n < 0:
            raise ValueError("counters only go up; use a Gauge")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Counter(_Metric):
    kind = "counter"

    def _make_child(self):
        return _CounterChild()

    def inc(self, n: float = 1.0) -> None:
        self._default().inc(n)

    @property
    def value(self) -> float:
        return self._default().value


class _GaugeChild:
    __slots__ = ("_value", "_fns", "_lock")

    def __init__(self):
        self._value = 0.0
        self._fns: List[Callable[[], float]] = []
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)
            self._fns = []

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)

    def set_function(self, fn: Callable[[], float]) -> None:
        """Callback gauge: sampled at render/snapshot time (queue depth,
        breaker trip counts — values owned elsewhere).  Replaces any
        providers registered so far; use `add_function` to accumulate."""
        with self._lock:
            self._fns = [fn]

    def add_function(self, fn: Callable[[], float]) -> None:
        """Register an ADDITIONAL provider: the gauge samples as the sum
        of all providers, so several engines sharing one registry each stay
        visible instead of the last registration silently winning."""
        with self._lock:
            if fn not in self._fns:
                self._fns.append(fn)

    def remove_function(self, fn: Callable[[], float]) -> None:
        """Drop a provider (no-op when absent) — called on engine shutdown
        so a stopped engine neither skews the sum nor stays reachable from
        a shared registry."""
        with self._lock:
            if fn in self._fns:
                self._fns.remove(fn)

    @property
    def value(self) -> float:
        with self._lock:
            fns = list(self._fns)
            if not fns:
                return self._value
        total, live = 0.0, 0
        for fn in fns:
            try:
                v = float(fn())
            except Exception:  # noqa: BLE001 — a dead backend must not kill
                continue       # the whole exposition
            if v != v:         # NaN: that provider's backend is down —
                continue       # don't blind the sum to the healthy ones
            total += v
            live += 1
        return total if live else float("nan")


class Gauge(_Metric):
    kind = "gauge"

    def _make_child(self):
        return _GaugeChild()

    def set(self, v: float) -> None:
        self._default().set(v)

    def inc(self, n: float = 1.0) -> None:
        self._default().inc(n)

    def dec(self, n: float = 1.0) -> None:
        self._default().dec(n)

    def set_function(self, fn: Callable[[], float]) -> None:
        self._default().set_function(fn)

    def add_function(self, fn: Callable[[], float]) -> None:
        self._default().add_function(fn)

    def remove_function(self, fn: Callable[[], float]) -> None:
        self._default().remove_function(fn)

    @property
    def value(self) -> float:
        return self._default().value


class _HistogramChild:
    __slots__ = ("_buckets", "_counts", "_sum", "_count", "_samples", "_lock")

    def __init__(self, buckets: Sequence[float], reservoir: int):
        self._buckets = tuple(buckets)          # sorted, no +Inf
        self._counts = [0] * (len(self._buckets) + 1)   # +Inf last
        self._sum = 0.0
        self._count = 0
        self._samples: deque = deque(maxlen=reservoir)
        self._lock = threading.Lock()

    def observe(self, v: float, n: int = 1) -> None:
        """Record one value; ``n > 1`` weights it as n samples (a batch
        whose records share the same latency — StageStats semantics)."""
        v = float(v)
        i = 0
        for i, ub in enumerate(self._buckets):
            if v <= ub:
                break
        else:
            i = len(self._buckets)
        with self._lock:
            self._counts[i] += n
            self._sum += v * n
            self._count += n
            self._samples.extend([v] * n)

    def observe_many(self, values: Sequence[float]) -> None:
        """Record a batch of distinct values under ONE lock acquisition.
        The per-tenant request-latency hop sits on the engine write
        worker's critical path; charging a flush's records one
        observe() at a time pays the lock and reservoir churn per
        record instead of per flush."""
        if not values:
            return
        nb = len(self._buckets)
        idxs, vals = [], []
        for v in values:
            v = float(v)
            i = 0
            for i, ub in enumerate(self._buckets):
                if v <= ub:
                    break
            else:
                i = nb
            idxs.append(i)
            vals.append(v)
        with self._lock:
            for i in idxs:
                self._counts[i] += 1
            self._sum += sum(vals)
            self._count += len(vals)
            self._samples.extend(vals)

    # StageStats-compatible alias: the engine's stage timers call record()
    record = observe

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def recent(self) -> List[float]:
        """The bounded reservoir of recent raw samples (tbwriter mirroring,
        trace-free percentile checks)."""
        with self._lock:
            return list(self._samples)

    def state(self) -> Tuple[Tuple[float, ...], List[int], float, int]:
        """(bucket bounds, per-bucket counts incl. +Inf, sum, count) — one
        consistent read for the Prometheus renderer."""
        with self._lock:
            return self._buckets, list(self._counts), self._sum, self._count

    def percentiles(self, qs: Sequence[float] = (50, 95, 99)) -> Dict:
        samples = sorted(self.recent())
        if not samples:
            return {f"p{int(q) if q == int(q) else q}": None for q in qs}
        return {f"p{int(q) if q == int(q) else q}": _percentile(samples, q)
                for q in qs}

    def snapshot(self) -> Dict:
        """The StageStats document, byte-compatible with PR 3's metrics
        surface: count, cumulative seconds, and mean/p50/p99 in ms over the
        recent-sample reservoir."""
        with self._lock:
            samples = list(self._samples)
            count, total = self._count, self._sum
        doc = {"count": count, "total_s": round(total, 6)}
        if samples:
            ms = sorted(s * 1e3 for s in samples)
            doc["mean_ms"] = round(sum(ms) / len(ms), 3)
            doc["p50_ms"] = round(_percentile(ms, 50), 3)
            doc["p99_ms"] = round(_percentile(ms, 99), 3)
        else:
            doc["mean_ms"] = doc["p50_ms"] = doc["p99_ms"] = None
        return doc


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 labelnames: Sequence[str] = (),
                 buckets: Sequence[float] = DEFAULT_BUCKETS,
                 reservoir: int = 2048):
        super().__init__(name, help, labelnames)
        self.buckets = tuple(sorted(float(b) for b in buckets))
        if not self.buckets:
            raise ValueError("histogram needs at least one bucket bound")
        self.reservoir = int(reservoir)

    def _make_child(self):
        return _HistogramChild(self.buckets, self.reservoir)

    def observe(self, v: float, n: int = 1) -> None:
        self._default().observe(v, n=n)

    record = observe

    @property
    def count(self) -> int:
        return self._default().count

    @property
    def sum(self) -> float:
        return self._default().sum

    def recent(self) -> List[float]:
        return self._default().recent()

    def percentiles(self, qs: Sequence[float] = (50, 95, 99)) -> Dict:
        return self._default().percentiles(qs)

    def snapshot(self) -> Dict:
        return self._default().snapshot()


class MetricsRegistry:
    """Named metric store.  ``counter``/``gauge``/``histogram`` are
    get-or-create: re-registering the same name with the same kind and
    labels returns the existing metric (each serving worker, the inference
    model, and the trainer can all ask for their metrics without
    coordinating); a kind or label mismatch raises."""

    def __init__(self):
        self._metrics: Dict[str, _Metric] = {}
        self._lock = threading.Lock()

    def _get_or_create(self, cls, name: str, help: str,
                       labelnames: Sequence[str], **kw) -> _Metric:
        labelnames = tuple(labelnames)
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if not isinstance(m, cls) or m.labelnames != labelnames:
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{m.kind}{m.labelnames}, wanted "
                        f"{cls.kind}{labelnames}")
                return m
            m = self._metrics[name] = cls(name, help, labelnames, **kw)
            return m

    def counter(self, name: str, help: str = "",
                labels: Sequence[str] = ()) -> Counter:
        return self._get_or_create(Counter, name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Sequence[str] = (),
              fn: Optional[Callable[[], float]] = None) -> Gauge:
        g = self._get_or_create(Gauge, name, help, labels)
        if fn is not None and not labels:
            # additive: a second registrant (another engine pooling into
            # this registry) joins the sum instead of clobbering the first
            g.add_function(fn)
        return g

    def histogram(self, name: str, help: str = "",
                  labels: Sequence[str] = (),
                  buckets: Optional[Sequence[float]] = None,
                  reservoir: Optional[int] = None) -> Histogram:
        m = self._get_or_create(
            Histogram, name, help, labels,
            buckets=DEFAULT_BUCKETS if buckets is None else buckets,
            reservoir=2048 if reservoir is None else reservoir)
        # get-or-create returns the existing metric: explicitly requested
        # buckets/reservoir that disagree with it would silently land every
        # observation in the wrong series — refuse like a kind mismatch.
        # (omitting the arguments means "whatever is registered")
        if buckets is not None and \
                tuple(sorted(float(b) for b in buckets)) != m.buckets:
            raise ValueError(
                f"metric {name!r} already registered with buckets "
                f"{m.buckets}, wanted {tuple(buckets)}")
        if reservoir is not None and int(reservoir) != m.reservoir:
            raise ValueError(
                f"metric {name!r} already registered with reservoir "
                f"{m.reservoir}, wanted {reservoir}")
        return m

    def get(self, name: str) -> Optional[_Metric]:
        with self._lock:
            return self._metrics.get(name)

    def metrics(self) -> List[_Metric]:
        with self._lock:
            return list(self._metrics.values())

    # -- snapshots ------------------------------------------------------------
    def snapshot(self) -> Dict:
        """JSON document: {name: {type, help, values: [{labels, ...}]}} —
        the machine-readable sibling of the Prometheus text."""
        out: Dict = {}
        for m in self.metrics():
            vals = []
            for key, child in m.children():
                labels = dict(zip(m.labelnames, key))
                if m.kind == "histogram":
                    _, counts, total, count = child.state()
                    vals.append(dict(labels=labels, count=count,
                                     sum=round(total, 9),
                                     **{k: v for k, v in
                                        child.snapshot().items()
                                        if k not in ("count", "total_s")}))
                else:
                    vals.append({"labels": labels, "value": child.value})
            out[m.name] = {"type": m.kind, "help": m.help, "values": vals}
        return out

    # -- Prometheus text exposition format v0.0.4 -----------------------------
    CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

    def to_prometheus(self) -> str:
        lines: List[str] = []
        for m in self.metrics():
            lines.append(f"# HELP {m.name} {_escape_help(m.help)}")
            lines.append(f"# TYPE {m.name} {m.kind}")
            for key, child in sorted(m.children(), key=lambda kv: kv[0]):
                pairs = [f'{ln}="{_escape_label(v)}"'
                         for ln, v in zip(m.labelnames, key)]
                if m.kind == "histogram":
                    bounds, counts, total, count = child.state()
                    cum = 0
                    for ub, c in zip(list(bounds) + [float("inf")], counts):
                        cum += c
                        lbl = ",".join(pairs + [f'le="{_fmt(ub)}"'])
                        lines.append(f"{m.name}_bucket{{{lbl}}} {cum}")
                    suffix = "{" + ",".join(pairs) + "}" if pairs else ""
                    lines.append(f"{m.name}_sum{suffix} {_fmt(total)}")
                    lines.append(f"{m.name}_count{suffix} {count}")
                else:
                    suffix = "{" + ",".join(pairs) + "}" if pairs else ""
                    lines.append(f"{m.name}{suffix} {_fmt(child.value)}")
        return "\n".join(lines) + "\n"


# -- process-wide default registry/tracer --------------------------------------

_global_registry: Optional[MetricsRegistry] = None
_global_tracer: Optional["Tracer"] = None
_global_recorder: Optional["FlightRecorder"] = None
_global_lock = threading.Lock()


def get_registry() -> MetricsRegistry:
    """The process-wide registry (training, standalone inference).  Serving
    engines default to their OWN registry instance so per-engine counters and
    stage percentiles stay attributable; pass ``registry=get_registry()`` to
    pool them."""
    global _global_registry
    with _global_lock:
        if _global_registry is None:
            _global_registry = MetricsRegistry()
        return _global_registry


def get_tracer() -> "Tracer":
    global _global_tracer
    with _global_lock:
        if _global_tracer is None:
            _global_tracer = Tracer()
        return _global_tracer


def get_recorder() -> "FlightRecorder":
    """The process-wide flight recorder (PR 15).  ONE ring per process by
    design: a replica process has one engine, and cross-layer emitters
    (AOT compile listeners, the LB, the supervisor) must land in the same
    ring the manager loop drains — events carry a ``replica`` attr when
    several engines share a test process."""
    global _global_recorder
    with _global_lock:
        if _global_recorder is None:
            _global_recorder = FlightRecorder()
        return _global_recorder


# -- incident flight recorder (PR 15) ------------------------------------------

class FlightRecorder:
    """Bounded in-process ring of typed events — the serving black box.

    An event is a plain dict ``{"event": kind, "ts": monotonic seconds,
    ...attrs}``; ``record()`` is the hot-path call, so it does the minimum
    under its lock (one deque append — the deque's maxlen evicts the
    oldest entry for free).  ``drain_events()`` is the atomic take+clear
    export hop the manager's spool loop calls, mirroring
    ``Tracer.drain_spans()`` so event spools ride the exact same
    rotation/clock-normalization contract as trace spools
    (``serving/tracecollect``).  ``recorded``/``dropped`` make ring
    pressure itself observable: a ring too small for the drain period
    shows up as a dropped count, not silent amnesia."""

    DEFAULT_MAXLEN = 4096

    def __init__(self, maxlen: int = DEFAULT_MAXLEN,
                 replica_id: Optional[str] = None):
        self._events: deque = deque(maxlen=max(16, int(maxlen)))
        self._lock = threading.Lock()
        self.replica_id = replica_id
        self.recorded = 0        # lifetime events seen
        self.dropped = 0         # evicted before a drain saw them

    @property
    def maxlen(self) -> int:
        return self._events.maxlen or 0

    def resize(self, maxlen: int) -> None:
        """Re-bound the ring (config ``recorder_ring``), keeping the most
        recent events."""
        maxlen = max(16, int(maxlen))
        with self._lock:
            if maxlen == self._events.maxlen:
                return
            self._events = deque(self._events, maxlen=maxlen)

    def record(self, kind: str, **attrs) -> Dict:
        """Append one event.  Attrs must be JSON-safe scalars/short
        strings — the spool writer downgrades anything else.  Never
        raises: the recorder is diagnostic, not load-bearing."""
        ev = {"event": str(kind), "ts": time.monotonic()}
        if self.replica_id is not None:
            ev["replica_id"] = self.replica_id
        for k, v in attrs.items():
            if v is not None:
                ev[k] = v
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(ev)
            self.recorded += 1
        return ev

    def events(self, kind: Optional[str] = None) -> List[Dict]:
        with self._lock:
            out = list(self._events)
        if kind is not None:
            out = [e for e in out if e.get("event") == kind]
        return out

    def drain_events(self) -> List[Dict]:
        """Atomically take every buffered event and clear the ring — the
        export hop the manager spool loop calls
        (``tracecollect.append_events``)."""
        with self._lock:
            out = list(self._events)
            self._events.clear()
        return out

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def stats(self) -> Dict:
        with self._lock:
            return {"buffered": len(self._events),
                    "maxlen": self._events.maxlen,
                    "recorded": self.recorded,
                    "dropped": self.dropped}


# -- per-process resource accounting (PR 15 satellite) --------------------------

def process_stats() -> Dict:
    """RSS bytes, cumulative CPU seconds, open FDs and thread count for
    THIS process — the per-process half of the resource ledger, read from
    /proc on Linux with ``resource``-module fallbacks elsewhere.  Any
    field that cannot be read reports None instead of raising: this runs
    on every /healthz scrape."""
    rss = cpu = fds = threads = None
    try:
        with open("/proc/self/statm") as f:
            rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource as _res
        ru = _res.getrusage(_res.RUSAGE_SELF)
        cpu = float(ru.ru_utime + ru.ru_stime)
        if rss is None and ru.ru_maxrss:
            rss = int(ru.ru_maxrss) * 1024    # peak, the portable fallback
    except Exception:  # noqa: BLE001 — non-POSIX
        pass
    try:
        fds = len(os.listdir("/proc/self/fd"))
    except OSError:
        pass
    try:
        threads = threading.active_count()
    except Exception:  # noqa: BLE001
        pass
    return {"rss_bytes": rss, "cpu_seconds": cpu,
            "open_fds": fds, "threads": threads}


# -- tracing -------------------------------------------------------------------

def new_trace_id() -> str:
    """128-bit random id, truncated to 16 hex chars (Dapper-style): stamped
    on the record at client enqueue, carried on every span and on
    quarantine/shed error results so one slow or poisoned record is
    greppable end to end."""
    return uuid.uuid4().hex[:16]


def new_span_id() -> str:
    """64-bit random span id (16 hex chars, the W3C parent-id width)."""
    return uuid.uuid4().hex[:16]


def trace_sampled(trace_id: Optional[str], rate: float) -> bool:
    """Head-sampling verdict as a PURE function of the trace_id: every
    process in the fleet (LB, gateway, engine, scheduler) reaches the SAME
    keep/drop decision for one trace without any coordination or header —
    hash the id into [0, 1) and compare against the rate.  ``rate >= 1``
    keeps everything (the fast path serving compiles down to), ``<= 0``
    drops everything; an unhashable/absent id is kept (better a stray span
    than a hole in a kept trace)."""
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    if not trace_id:
        return True
    try:
        h = int(str(trace_id)[-8:], 16)
    except ValueError:
        h = -1
    if h < 0:
        # non-hex tail, OR a client-controlled id ending in "-hhhhhhh"
        # (int() accepts a sign, and a negative hash is < every rate —
        # an always-sampled bypass of the volume cap): hash honestly
        import zlib
        h = zlib.crc32(str(trace_id).encode("utf-8")) & 0xFFFFFFFF
    return (h / float(0x100000000)) < rate


class SpanContext:
    """Propagated trace context (trace_id, span_id, sampled flag) with the
    W3C ``traceparent`` serialization::

        00-<32-hex trace-id>-<16-hex span-id>-<2-hex flags>

    The platform's 16-hex trace ids are left-padded to the 32-hex W3C
    field on the wire and stripped back on parse (a genuinely 32-hex
    foreign id is kept verbatim), so cross-vendor headers interoperate
    while every in-platform surface keeps the compact id it logs today.
    ``child()`` mints the next hop's context: same trace, fresh span id,
    inherited sampling verdict — the minted span_id is the PARENT the next
    process stamps on its spans."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: Optional[str] = None,
                 span_id: Optional[str] = None, sampled: bool = True):
        self.trace_id = trace_id or new_trace_id()
        self.span_id = span_id or new_span_id()
        self.sampled = bool(sampled)

    def child(self) -> "SpanContext":
        return SpanContext(self.trace_id, new_span_id(), self.sampled)

    def to_traceparent(self) -> str:
        flags = 0x01 if self.sampled else 0x00
        return (f"00-{str(self.trace_id).zfill(32)}-"
                f"{str(self.span_id).zfill(16)}-{flags:02x}")

    @classmethod
    def from_traceparent(cls, value) -> Optional["SpanContext"]:
        """Parse a ``traceparent`` header; None on anything malformed (an
        untrusted remote header must degrade to a fresh root, never an
        exception on the ingest path)."""
        if not isinstance(value, str):
            return None
        parts = value.strip().lower().split("-")
        if len(parts) < 4:
            return None
        version, trace, span, flags = parts[0], parts[1], parts[2], parts[3]
        if len(version) != 2 or len(trace) != 32 or len(span) != 16:
            return None
        try:
            int(version, 16)
            int(trace, 16)
            int(span, 16)
            fl = int(flags[:2], 16)
        except ValueError:
            return None
        if version == "ff" or int(trace, 16) == 0 or int(span, 16) == 0:
            return None
        # strip the in-platform left-pad; keep foreign 32-hex ids verbatim
        if trace.startswith("0" * 16):
            trace = trace[16:]
        return cls(trace, span, sampled=bool(fl & 0x01))


class Tracer:
    """Bounded ring buffer of spans.  A span is a plain dict:
    ``{trace_id, uri, stage, ts, dur_s, span_id?, parent_id?, replica_id?,
    error?, ...attrs}`` with ``ts`` on the monotonic clock
    (self-consistent within one process; ``serving/tracecollect.py``
    normalizes across processes via each replica's wall/monotonic clock
    pair).  ``chrome_trace()`` renders the Perfetto / ``chrome://tracing``
    event-list form.

    Error spans (quarantine/shed) additionally land in a SMALL separate
    bounded buffer: under generation load the ring churns at per-boundary
    decode-span rate and would evict the one rare error span being
    diagnosed — the side buffer keeps the last ``error_maxlen`` of them
    alive until the next ``drain_spans()`` regardless of ring pressure."""

    def __init__(self, maxlen: int = 8192, replica_id: Optional[str] = None,
                 error_maxlen: int = 256):
        self._spans: deque = deque(maxlen=maxlen)
        # survival buffer for error spans only (see class docstring)
        self._error_spans: deque = deque(maxlen=error_maxlen)
        self._lock = threading.Lock()
        self.replica_id = replica_id

    new_trace_id = staticmethod(new_trace_id)
    new_span_id = staticmethod(new_span_id)

    def span(self, stage: str, t0_s: float, t1_s: float,
             trace_id: Optional[str] = None, uri=None,
             error: Optional[str] = None,
             span_id: Optional[str] = None,
             parent_id: Optional[str] = None,
             attrs: Optional[Dict] = None) -> Dict:
        s = {"trace_id": trace_id, "uri": uri, "stage": stage,
             "ts": float(t0_s), "dur_s": max(float(t1_s) - float(t0_s), 0.0)}
        if span_id is not None:
            s["span_id"] = span_id
        if parent_id is not None:
            s["parent_id"] = parent_id
        if self.replica_id is not None:
            s["replica_id"] = self.replica_id
        if attrs:
            for k, v in attrs.items():
                s.setdefault(k, v)
        if error is not None:
            s["error"] = str(error)
        with self._lock:
            self._spans.append(s)
            if error is not None:
                self._error_spans.append(s)
        return s

    def _merged(self) -> List[Dict]:
        """Ring + error-buffer spans (lock held by caller): error spans
        evicted from the ring are appended after it, original order kept
        within each buffer, duplicates (still in both) reported once."""
        out = list(self._spans)
        ring_ids = {id(s) for s in out}
        out.extend(s for s in self._error_spans if id(s) not in ring_ids)
        return out

    def spans(self, trace_id: Optional[str] = None) -> List[Dict]:
        with self._lock:
            out = self._merged()
        if trace_id is not None:
            out = [s for s in out if s["trace_id"] == trace_id]
        return out

    def drain_spans(self) -> List[Dict]:
        """Atomically take every buffered span (ring AND the error side
        buffer) and clear both — the export hop the per-replica spool
        writers call (``serving/tracecollect.append_spans``).  Spans
        recorded concurrently land in the next drain."""
        with self._lock:
            out = self._merged()
            self._spans.clear()
            self._error_spans.clear()
        return out

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._error_spans.clear()

    def stages_for(self, trace_id: str) -> List[str]:
        return [s["stage"] for s in self.spans(trace_id)]

    def chrome_trace(self) -> Dict:
        """Chrome trace-event JSON (``ph: "X"`` complete events, µs units).
        One tid per stage so Perfetto lays the pipeline out as parallel
        tracks; trace_id/uri/error ride in ``args``."""
        pid = os.getpid()
        tids: Dict[str, int] = {}
        events = []
        for s in self.spans():
            tid = tids.setdefault(s["stage"], len(tids) + 1)
            ev = {"name": s["stage"], "cat": "serving", "ph": "X",
                  "ts": round(s["ts"] * 1e6, 3),
                  "dur": round(s["dur_s"] * 1e6, 3),
                  "pid": pid, "tid": tid,
                  "args": {"trace_id": s["trace_id"], "uri": s["uri"]}}
            # PR 13 fields (span/parent ids, replica identity, span attrs
            # like tokens-emitted) ride in args so Perfetto shows them
            for k, v in s.items():
                if k not in ("trace_id", "uri", "stage", "ts", "dur_s"):
                    ev["args"][k] = v
            events.append(ev)
        meta = [{"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                 "args": {"name": stage}} for stage, tid in tids.items()]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> str:
        doc = self.chrome_trace()
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path


class SpanTimer:
    """``with SpanTimer(tracer, "predict", trace_id=..., uri=...):`` — spans
    a code block; an escaping exception is recorded on the span and
    re-raised."""

    def __init__(self, tracer: Tracer, stage: str,
                 trace_id: Optional[str] = None, uri=None,
                 clock: Callable[[], float] = time.monotonic):
        self._tracer = tracer
        self.stage = stage
        self.trace_id = trace_id
        self.uri = uri
        self._clock = clock
        self._t0 = None

    def __enter__(self):
        self._t0 = self._clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        err = None if exc is None else f"{type(exc).__name__}: {exc}"
        self._tracer.span(self.stage, self._t0, self._clock(),
                          trace_id=self.trace_id, uri=self.uri, error=err)
        return False


# -- phase clock (PR 25) --------------------------------------------------------

class PhaseClock:
    """A partition of ONE thread's wall time into named phases.

    A switch, not a nest: ``to(name)`` reads the clock once, charges the
    time since the last switch to the phase that was current and makes
    ``name`` current, so there is always exactly one current phase and the
    phases' seconds sum to the time since construction — no gap, no double
    count.  ``with clock.phase(name):`` is the same switch that returns to
    the enclosing phase on the way out (exceptions included).

    ``annotate`` is a context-manager factory taking a span name
    (``jax.profiler.TraceAnnotation``, injected so this module stays
    stdlib-only; ``None`` = no annotation): every phase is opened as
    ``<prefix><name>``, so a profiler trace shows the phases on the owning
    thread's line, on the device's clock.  With no trace active an
    annotation is a flag test.

    Only the owning thread switches.  ``totals()`` may be called from any
    thread while the owner runs: it takes no lock (a reader that meets a
    switch in progress reads again) and includes the open phase up to now.
    """

    def __init__(self, phases: Sequence[str], annotate=None,
                 prefix: str = "", clock: Callable[[], float] = time.monotonic):
        self.phases = tuple(phases)
        self._names = {p: prefix + p for p in self.phases}
        self._annotate = annotate
        self._clock = clock
        self._seconds = {p: 0.0 for p in self.phases}
        self._counts = {p: 0 for p in self.phases}
        # the owner may be another thread than the constructor, so the
        # first phase gets no annotation: one is opened at the first switch
        self._span = None
        self._open = (self.phases[0], clock())     # (current phase, since)

    @property
    def current(self) -> str:
        return self._open[0]

    def to(self, name: str) -> None:
        label = self._names[name]      # an unknown phase fails here, at once
        prev, since = self._open
        now = self._clock()
        self._open = None              # a switch is in progress (totals)
        self._seconds[prev] += now - since
        self._counts[prev] += 1
        self._open = (name, now)
        if self._annotate is not None:
            if self._span is not None:
                self._span.__exit__(None, None, None)
            self._span = self._annotate(label)
            self._span.__enter__()

    @contextlib.contextmanager
    def phase(self, name: str):
        outer = self._open[0]
        self.to(name)
        try:
            yield
        finally:
            self.to(outer)

    def totals(self) -> Tuple[Dict[str, float], Dict[str, int]]:
        """``(seconds, counts)`` by phase: ``counts`` are completed visits,
        ``seconds`` include the open phase up to now, so their sum is the
        time since construction."""
        while True:
            open_ = self._open
            seconds, counts = dict(self._seconds), dict(self._counts)
            if open_ is not None and self._open is open_:
                break
        seconds[open_[0]] += self._clock() - open_[1]
        return seconds, counts


# -- start-up marks (PR 37) -----------------------------------------------------

class StartupMarks:
    """Named instants of one start, ``time.monotonic()`` seconds; the first
    stamp of a name wins, so a mark says when its stage was FIRST reached.

    The process-wide object (``get_startup()``) holds ``imported`` (end of
    ``analytics_zoo_tpu/__init__.py``) and ``model_loaded``
    (``InferenceModel.do_load_model``).  A serving engine starts its own
    from those (``StartupMarks(get_startup().snapshot())``) and stamps
    ``engine``, ``warm_begin``, ``ready`` and ``first_result`` there, so
    that several engines of one process each keep their own."""

    ORDER = ("imported", "model_loaded", "engine", "warm_begin", "ready",
             "first_result")

    def __init__(self, marks: Optional[Dict[str, float]] = None):
        self._t: Dict[str, float] = dict(marks or {})

    def stamp(self, name: str, t: Optional[float] = None) -> float:
        """Set ``name`` to ``t`` (default: now) unless it is set; returns
        the mark's value either way."""
        if t is None:
            t = time.monotonic()
        return self._t.setdefault(name, t)      # atomic: first stamp wins

    def get(self, name: str) -> Optional[float]:
        return self._t.get(name)

    def snapshot(self) -> Dict[str, float]:
        """The marks set so far, in ``ORDER`` (others after)."""
        t = dict(self._t)
        return {k: t[k] for k in (*self.ORDER, *t) if k in t}


_global_startup = StartupMarks()


def get_startup() -> StartupMarks:
    """The process-wide start-up marks (see ``StartupMarks``)."""
    return _global_startup


# -- SLO attribution (PR 13) ---------------------------------------------------

class SloTracker:
    """Latency-objective bookkeeping for one serving replica: every
    completed record's end-to-end latency is judged against the objective,
    a violation is ATTRIBUTED to its dominant pipeline stage
    (``serving_slo_violations_total{stage=}`` — "we missed the SLO because
    of queue-wait", not just "we missed"), and a rolling window drives the
    burn-rate gauge::

        burn = violating fraction over the window / error budget

    where the error budget is ``1 - target`` (target 0.99 -> budget 1%; a
    burn rate of 1.0 means the budget is being spent exactly as fast as it
    accrues, >1 means the SLO will be blown).  Counters/gauges land in the
    registry the engine exports, so the fleet metrics merge aggregates
    them like every other serving series (burn rate merges as MAX — see
    ``serving/fleet.py``)."""

    def __init__(self, registry: MetricsRegistry, latency_ms: float,
                 window_s: float = 60.0, target: float = 0.99,
                 tenant: Optional[str] = None):
        self.latency_ms = float(latency_ms)
        self.window_s = max(1.0, float(window_s))
        self.target = min(max(float(target), 0.0), 0.999999)
        self.tenant = tenant
        # The burn-rate family is registered labeled; the fleet-global
        # tracker publishes through the BARE child (exposition unchanged:
        # ``serving_slo_burn_rate 2.0``), per-tenant trackers (PR 19)
        # through ``{tenant=...}`` children of the same family.
        g = registry.gauge(
            "serving_slo_burn_rate",
            "Error-budget burn rate over the SLO window "
            "(1.0 = spending the budget exactly as it accrues)",
            labels=("tenant",))
        self._g_burn = g.labels(tenant=tenant) if tenant else g.bare()
        self._g_burn.set(0.0)
        if tenant is None:
            self._m_violations = registry.counter(
                "serving_slo_violations_total",
                "Latency-SLO violations, attributed to the dominant stage",
                labels=("stage",))
            # materialized at zero for the stages every deployment has, so
            # the series are scrapeable before the first violation
            for stage in ("queue_wait", "predict", "write", "pipeline",
                          "decode"):
                self._m_violations.labels(stage=stage).inc(0)
            self._g_objective = registry.gauge(
                "serving_slo_latency_objective_ms",
                "Configured latency objective")
            self._g_objective.set(self.latency_ms)
        else:
            # per-tenant views share the fleet-global stage attribution;
            # registering a second {stage=} counter here would double-count
            self._m_violations = None
            self._g_objective = None
        self._window: deque = deque()      # (monotonic ts, violated: bool)
        self._lock = threading.Lock()

    @classmethod
    def from_config(cls, registry: MetricsRegistry,
                    cfg: Optional[Dict]) -> Optional["SloTracker"]:
        """``serving_slo:`` config block -> tracker (None when absent or
        unusable): ``{latency_ms: 500, window_s: 60, target: 0.99}``."""
        if not isinstance(cfg, dict):
            return None
        try:
            latency_ms = float(cfg["latency_ms"])
        except (KeyError, TypeError, ValueError):
            return None
        if latency_ms <= 0:
            return None
        try:
            window_s = float(cfg.get("window_s", 60.0))
            target = float(cfg.get("target", 0.99))
        except (TypeError, ValueError):
            window_s, target = 60.0, 0.99
        return cls(registry, latency_ms, window_s=window_s, target=target)

    def observe(self, e2e_s: float, stages: Optional[Dict] = None,
                now: Optional[float] = None) -> Optional[str]:
        """Judge one completed record.  ``stages`` maps stage name ->
        seconds spent there; on a violation the LARGEST contributor is
        charged.  Returns the charged stage (None = no violation)."""
        now = time.monotonic() if now is None else float(now)
        violated = float(e2e_s) * 1e3 > self.latency_ms
        charged = None
        if violated:
            valid = {k: float(v) for k, v in (stages or {}).items()
                     if isinstance(v, (int, float)) and v == v and v >= 0}
            charged = max(valid, key=valid.get) if valid else "unattributed"
            if self._m_violations is not None:
                self._m_violations.labels(stage=charged).inc()
        with self._lock:
            self._window.append((now, violated))
            cutoff = now - self.window_s
            while self._window and self._window[0][0] < cutoff:
                self._window.popleft()
            total = len(self._window)
            bad = sum(1 for _, v in self._window if v)
        budget = 1.0 - self.target
        self._g_burn.set((bad / total) / budget if total else 0.0)
        return charged

    def snapshot(self) -> Dict:
        with self._lock:
            total = len(self._window)
            bad = sum(1 for _, v in self._window if v)
        return {"latency_ms": self.latency_ms,
                "window_s": self.window_s,
                "target": self.target,
                "window_records": total,
                "window_violations": bad,
                "burn_rate": round(self._g_burn.value, 4)}
