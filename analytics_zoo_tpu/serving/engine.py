"""Cluster Serving engine — queue → batcher → TPU predict → result store.

Reference parity: `ClusterServing.main` (serving/ClusterServing.scala:34-352): a
streaming micro-batch loop reading the Redis stream, batching to `batch_size`,
pre-processing base64 images, broadcast-model predict, top-N post-processing, writing
the result table with back-pressure, XTRIM memory guard, and throughput scalars
(`Serving Throughput`, `Total Records Number`) to TensorBoard.

TPU-native: the "broadcast model" is just the jitted predict function; batching pads to
power-of-two buckets (InferenceModel) so the compile cache stays tiny; the micro-batch
loop is a plain thread, not a Spark Structured Streaming job.

Resilience (PR 1): the reference delegated failure recovery to Spark
Structured Streaming restarts; here the worker loops run under
`SupervisedThread` (crash -> log -> backoff -> restart, capped), one
malformed record quarantines ONLY itself to the queue's dead-letter channel
(the client sees an `{"error": ...}` result instead of hanging), a predict
crash bisects the batch to isolate the poison input, and result writes go
through a `RetryPolicy` + `CircuitBreaker` instead of the old ad-hoc loop.
`ClusterServing.health()` reports worker/breaker/dead-letter state.

Throughput data plane (PR 3): the reference leaned on Spark Structured
Streaming for micro-batch coalescing and parallel executors; the rebuilt
loop gets the same effects natively:

- **adaptive micro-batching** — `_read_coalesced` fills device-sized
  batches (`max_batch`) under load, waiting at most `max_wait_ms` once the
  first record of a partial batch has arrived; an idle stream still returns
  within `poll_timeout_s`, so latency stays low when traffic is light.
- **parallel preprocess** — `preprocess_workers > 1` fans the per-record
  decode (base64 + cv2, the measured host bottleneck) across a thread pool;
  per-record quarantine and shape re-grouping semantics are unchanged.
- **async device pipeline** — the predict worker DISPATCHES batches
  (`InferenceModel.dispatch`, no host readback) and hands the in-flight
  handle to a downstream write worker; up to `inflight_batches` batches
  overlap device compute with both preprocess and result writing.
- **batched result writes** — one `queue.put_results(pairs)` round-trip per
  micro-batch (Redis pipeline-style `hset` mapping / FileQueue batch spool /
  InProc bulk), falling back to per-record writes under the existing
  RetryPolicy + CircuitBreaker when a batch write fails; `trim()` runs on an
  amortized `trim_interval_s` schedule instead of once per batch.
- **per-stage metrics** — read/preprocess/stage-wait/predict/write timers
  plus end-to-end (read -> result written) p50/p99 latency, exposed through
  `metrics()`/`/metrics` and carried on the `health()` document, so the
  bottleneck is measured rather than inferred.

Unified telemetry (PR 4): the bespoke `StageStats` reservoirs are replaced
by `common/observability.py` registry primitives — every stage timer is a
labeled `Histogram` (`serving_stage_seconds{stage=...}`), quarantine/shed/
record counts are `Counter`s, queue depth / restarts / breaker trips are
callback `Gauge`s — and the whole registry renders as Prometheus text
exposition via `/metrics?format=prom` (the JSON document is unchanged).  A
`Tracer` records one span per pipeline stage per record, keyed by the
`trace_id` the client stamped at enqueue (riding the wire next to
`deadline_ns`); quarantined and shed records get a span carrying the error,
so a single slow or poisoned record is diagnosable by trace_id
(`ClusterServing.export_trace()` dumps Chrome trace-event JSON that
`tools/trace_view.py` summarizes).

Horizontal replicas (PR 5): the engine is now one of N crash-tolerant
replicas over a shared queue.  Reads CLAIM records under a lease instead of
destroying them; the claim is released (`queue.ack`) only after the record's
result — value, quarantine error, or deadline-shed marker — is written, so
a SIGKILLed replica's in-flight records sit orphaned in the queue's pending
store instead of vanishing.  A periodic RECLAIM sweep
(`params.lease_s` / `params.reclaim_interval_s`) re-claims entries idle past
the lease and feeds them back through the normal pipeline: `trace_id` and
`deadline_ns` ride inside the record, so redelivered records shed at the
deadline gates and correlate in traces exactly like first deliveries.
Redelivered records that ALREADY have a result (the dead replica wrote it
but died before acking) are suppressed — acked without a second predict —
keeping the client contract at exactly one result per record on top of
at-least-once delivery.  Each engine carries a `replica_id` (health doc,
`X-Replica-Id` probe header, `serving_heartbeat_age_seconds{replica=}`
gauge); `serving_reclaimed_total{backend=}` and
`serving_duplicate_results_total` land in the same registry.

Sharded multi-chip serving (PR 6): with `params.sharding != "off"` the
engine shards its InferenceModel over a `data` x `model` device mesh at
construction (`InferenceModel.shard`): params are placed once, every padded
batch is committed with a batch-axis NamedSharding, and the SAME pipeline
(dispatch -> writer `.result()`, drain, bisect, int8 wire with per-row
scales) runs over all chips — the predict stage is the only thing that got
wider.  `auto` batch-shards small models and megatron tensor-shards large
transformer stacks; buckets round up to a multiple of the mesh batch axis
so padded batches split evenly.
"""

from __future__ import annotations

import base64
import itertools
import logging
import os
import threading
import time
from queue import Full as _FULL
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from analytics_zoo_tpu.common.observability import (MetricsRegistry,
                                                    SloTracker, SpanContext,
                                                    StartupMarks, Tracer,
                                                    get_startup, new_trace_id,
                                                    trace_sampled)
from analytics_zoo_tpu.common.resilience import (CircuitBreaker,
                                                 CircuitBreakerOpen,
                                                 RetryPolicy,
                                                 SupervisedThread,
                                                 wait_until)
from analytics_zoo_tpu.inference.inference_model import InferenceModel
from analytics_zoo_tpu.serving import wire as _wire
from analytics_zoo_tpu.serving.queues import BaseQueue

logger = logging.getLogger(__name__)


class QuantizedTensor(NamedTuple):
    """A tensor kept in its compact integer dtype until it is ON the
    accelerator (round 5): do_predict transfers the int8/uint8 bytes and
    dequantizes (x * scale) inside the jitted program — 4x less
    host->device traffic than f32, which matters wherever the host->device
    link is the binding constraint."""

    data: np.ndarray      # int8 / uint8
    scale: float


def _wire_fmt_label(record: Dict) -> str:
    """Metric label for a record's wire format.  The field is producer-
    controlled (raw xadd bypasses the gateway's stripping), so anything
    but the known binary tags folds into the json label: an unhashable
    value would dead-letter a valid record at the labels() call, and
    distinct strings would mint unbounded permanent metric series."""
    fmt = record.get("wire_fmt")
    return fmt if fmt in (_wire.FMT_BIN, _wire.FMT_SHM) else _wire.FMT_JSON


def _decode_tensor_record(record: Dict):
    """Binary-wire decode (PR 7 tentpole): materialize a frame-decoded
    record — inline ``payload`` memoryview or shared-memory slot reference
    — with ``np.frombuffer`` over the existing buffer.  ONE copy total (the
    float32 normalization every path needs, since frombuffer views are
    read-only) instead of the legacy path's base64 decode + reshape copies.
    A shm slot is re-verified AFTER the copy: a producer lapping the ring
    mid-read raises ``FrameError`` -> per-record quarantine, never torn
    bytes served as data."""
    view, shm_ref = _wire.resolve_payload(record)
    dtype = np.dtype(record.get("dtype", "<f4"))
    arr = np.frombuffer(view, dtype)
    if "shape" in record:
        arr = arr.reshape([int(s) for s in record["shape"]])
    if "scale" in record and record.get("dtype") == "<i1":
        out = QuantizedTensor(arr.astype(np.int8),
                              float(record["scale"]))
    elif "scale" in record:
        out = arr.astype(np.float32) * float(record["scale"])
    else:
        out = arr.astype(np.float32)
    _wire.COPY_STATS.record("normalize", arr.nbytes)
    if shm_ref is not None:
        # the copy above is the LAST touch of the slot: verify the
        # generation now so an overwrite during the read is detected
        _wire.attach_ring(shm_ref).verify(shm_ref)
    return out


def default_preprocess(record: Dict):
    """base64 bytes -> decoded image float (PreProcessing.scala:1-53), a
    QuantizedTensor for int8-wire / uint8-image records, raw tensor
    passthrough for `data` records, or — PR 7 — binary-frame records
    (``payload`` buffer / ``shm`` slot reference) via
    ``_decode_tensor_record``."""
    if "payload" in record or "shm" in record:
        return _decode_tensor_record(record)
    if "image" in record:
        import cv2
        buf = np.frombuffer(base64.b64decode(record["image"]), np.uint8)
        img = cv2.imdecode(buf, cv2.IMREAD_COLOR)
        if record.get("u8"):
            if "resize" in record:
                h, w = record["resize"]
                img = cv2.resize(img, (w, h))
            return QuantizedTensor(np.asarray(img, np.uint8), 1.0)
        # float path: convert BEFORE resizing (float interpolation), keeping
        # pre-round-5 numerics byte-identical
        img = img.astype(np.float32)
        if "resize" in record:
            h, w = record["resize"]
            img = cv2.resize(img, (w, h))
        return img
    if "b64" in record:
        # raw-bytes tensor (client.enqueue_tensor wire format); explicit
        # little-endian dtype tag so cross-endian pairs stay correct, and a
        # copy so downstream in-place normalization works (frombuffer views
        # are read-only)
        raw = base64.b64decode(record["b64"])
        _wire.COPY_STATS.record("b64_decode", len(raw))
        arr = np.frombuffer(raw, np.dtype(record.get("dtype", "<f4")))
        if "shape" in record:
            arr = arr.reshape([int(s) for s in record["shape"]])
        if "scale" in record:
            # int8 wire: stay int8 until on device.  Gated on the declared
            # dtype (ADVICE r5): a float record carrying a stray `scale`
            # must be dequantized on host, not truncated by astype(int8).
            if record.get("dtype") == "<i1":
                out = QuantizedTensor(arr.astype(np.int8),
                                      float(record["scale"]))
            else:
                out = arr.astype(np.float32) * float(record["scale"])
        else:
            out = arr.astype(np.float32)
        _wire.COPY_STATS.record("normalize", arr.nbytes)
        return out
    if "data" in record:
        arr = np.asarray(record["data"], np.float32)
        if "shape" in record:
            arr = arr.reshape(record["shape"])
        return arr
    raise ValueError(f"record has neither image nor data: {list(record)}")


def default_postprocess(probs: np.ndarray, top_n: int = 5) -> List:
    """top-N (class, prob) pairs (PostProcessing.scala:1-117).

    O(n) selection: `np.argpartition` pulls the top slice, then only that
    slice is sorted — at classification widths (1k-20k classes) this beats
    the previous full `np.argsort` (O(n log n)) per record on the serving
    write path."""
    n = probs.shape[-1]
    if top_n >= n:
        idx = np.argsort(-probs)
    else:
        part = np.argpartition(-probs, top_n)[:top_n]
        idx = part[np.argsort(-probs[part])]
    return [[int(i), float(probs[i])] for i in idx]


# StageStats (PR 3) is gone: the per-stage reservoirs are now labeled
# observability.Histogram children (`serving_stage_seconds{stage=...}`)
# whose .snapshot() emits the same {count,total_s,mean_ms,p50_ms,p99_ms}
# document, plus Prometheus _bucket/_sum/_count series for free.


class _Staged(NamedTuple):
    """One same-shape micro-batch staged between preprocess and predict.
    Field order is part of the internal API: `_predict_stage(*staged)`."""

    ids: List
    tensors: np.ndarray
    scales: Optional[np.ndarray]
    deadlines: Optional[List]
    traces: Optional[List]        # per-record trace_id (wire-stamped)
    t_read: Optional[float]       # monotonic: read_batch returned
    t_ready: Optional[float]      # monotonic: preprocess/grouping done
    metas: Optional[List] = None  # per-record `gen` options (PR 12), None
    #                               for the predict plane


class _InFlight(NamedTuple):
    """One dispatched batch between the predict and write workers.  Keeps
    the host-side tensors so a device failure surfacing at readback can
    still bisect-quarantine the poison row."""

    ids: List
    tensors: np.ndarray
    scales: Optional[np.ndarray]
    handle: "_ResultHandle"
    traces: Optional[List]
    t_read: Optional[float]
    t_dispatch: float
    tenants: Optional[List] = None  # per-row tenant (PR 19 attribution);
    #                                 None entries = legacy/unattributed


class _ResultHandle:
    """Deferred prediction result: `.result()` blocks on (and returns) the
    host value, re-raising any dispatch/compute failure there so the write
    stage owns the bisect fallback."""

    def result(self):
        raise NotImplementedError


class _LazyResult(_ResultHandle):
    """Synchronous fallback handle: the predict call itself is deferred to
    `.result()` (used when `do_predict` is instance-patched — chaos tests
    and user shims must stay on the hot path — or the model has no async
    `dispatch` entry point)."""

    def __init__(self, fn: Callable):
        self._fn = fn

    def result(self):
        return self._fn()


class _FailedDispatch(_ResultHandle):
    """A dispatch that raised synchronously (e.g. a shape-mismatch trace
    error): surfaces the exception at `.result()` like any other failure."""

    def __init__(self, exc: BaseException):
        self._exc = exc

    def result(self):
        raise self._exc


def resolve_quantize_spec(q) -> Optional[Dict]:
    """Normalize the `ServingParams.quantize` surface to a spec dict
    {"bits", "group_size", "percentile", "calib"} (or None = off).
    Accepts None/False, "int8"/"int4", 8/4, or a dict with those keys."""
    if not q:
        return None
    if isinstance(q, dict):
        spec = dict(q)
    elif q in ("int8", "int4", 8, 4, "8", "4", True):
        spec = {"bits": 8 if q in ("int8", 8, "8", True) else 4}
    else:
        raise ValueError(
            f"quantize={q!r}: expected int8|int4|8|4 or a spec dict")
    bits = int(spec.get("bits", 8))
    if bits not in (8, 4):
        raise ValueError(f"quantize.bits={bits!r}: expected 8 or 4")
    return {"bits": bits,
            "group_size": int(spec.get("group_size", 64)),
            "percentile": (None if spec.get("percentile") is None
                           else float(spec["percentile"])),
            "calib": spec.get("calib")}


def apply_quantize(model, spec) -> bool:
    """Quantize an InferenceModel per a (resolved) `quantize` spec — the
    ONE application path shared by ClusterServing construction and
    `manager warmup`, so the store the manager exports and the graph a
    replica serves are the same program family.  Returns True when the
    model was quantized here, False when it already was (a quantized
    mmap store restored at load — re-quantizing int8 leaves would stack
    errors).  An int8 spec on an unquantized model REQUIRES calibration
    data (`calib`: .npy one batch / .npz batch-per-entry): activation
    scales cannot be conjured, so this fails construction loudly."""
    from analytics_zoo_tpu.inference.quantize import quantized_bits
    spec = resolve_quantize_spec(spec)
    if spec is None:
        return False
    have = quantized_bits(getattr(model, "_params", None) or {})
    if have:
        if have != spec["bits"]:
            logger.warning(
                "serving: model already quantized at %d bits; ignoring "
                "the quantize=%d config (re-load float weights to "
                "re-quantize)", have, spec["bits"])
        return False
    calib = None
    if spec["calib"]:
        import numpy as _np
        loaded = _np.load(spec["calib"], allow_pickle=False)
        calib = [loaded[k] for k in loaded.files] \
            if hasattr(loaded, "files") else loaded
    if spec["bits"] == 8 and calib is None:
        raise ValueError(
            "quantize: int8 needs activation calibration — provide "
            "quantize.calib (.npy/.npz batch file), quantize offline via "
            "do_quantize(FeatureSet, bits=8), or serve a quantized "
            "weight store")
    model.do_quantize(calib, force=True, bits=spec["bits"],
                      group_size=spec["group_size"],
                      percentile=spec["percentile"])
    logger.info("serving: model quantized to int%d at construction "
                "(group_size=%d, percentile=%s, calib=%s)", spec["bits"],
                spec["group_size"], spec["percentile"], spec["calib"])
    return True


class ServingParams:
    """config.yaml surface (scripts/cluster-serving/config.yaml parity)."""

    def __init__(self, batch_size: int = 4, top_n: int = 5,
                 poll_timeout_s: float = 0.05, stream_max_len: int = 100000,
                 filter_threshold: Optional[float] = None,
                 write_retries: int = 5, write_backoff_s: float = 0.05,
                 pipeline_depth: int = 2,
                 max_worker_restarts: int = 5,
                 worker_backoff_s: float = 0.05,
                 breaker_threshold: int = 5,
                 breaker_cooldown_s: float = 0.5,
                 http_port: Optional[int] = None,
                 http_host: str = "127.0.0.1",
                 drain_s: Optional[float] = None,
                 ready_queue_depth: Optional[int] = None,
                 max_batch: Optional[int] = None,
                 max_wait_ms: float = 5.0,
                 preprocess_workers: int = 1,
                 inflight_batches: int = 2,
                 trim_interval_s: float = 5.0,
                 tracing: bool = True,
                 replica_id: Optional[str] = None,
                 lease_s: float = 30.0,
                 reclaim_interval_s: Optional[float] = None,
                 max_deliveries: int = 5,
                 mesh_shape=None,
                 sharding: str = "off",
                 gateway: bool = True,
                 warmup=False,
                 compile_cache_dir: Optional[str] = None,
                 generation=None,
                 trace_sample: float = 1.0,
                 serving_slo=None,
                 quantize=None,
                 flight_recorder: bool = True,
                 recorder_ring: Optional[int] = None,
                 profiling: bool = True,
                 model_version: Optional[str] = None,
                 faults=None,
                 admission=None,
                 brownout=None,
                 metering=None):
        self.batch_size = batch_size
        self.top_n = top_n
        self.poll_timeout_s = poll_timeout_s
        self.stream_max_len = stream_max_len
        self.filter_threshold = filter_threshold
        # result-write backpressure (ClusterServing.scala:276-307 analog)
        self.write_retries = write_retries
        self.write_backoff_s = write_backoff_s
        # staged micro-batches between the host preprocess thread and the
        # device predict thread; bounds memory AND provides backpressure
        self.pipeline_depth = pipeline_depth
        # worker supervision + queue-write circuit breaker (PR 1 resilience)
        self.max_worker_restarts = max_worker_restarts
        self.worker_backoff_s = worker_backoff_s
        self.breaker_threshold = breaker_threshold
        self.breaker_cooldown_s = breaker_cooldown_s
        # availability layer (PR 2): HTTP probes (/healthz /readyz /metrics;
        # None = off, 0 = ephemeral port), graceful-drain budget used by the
        # manager's SIGTERM handler, and the /readyz queue-depth threshold
        # (None falls back to the queue's own max_depth admission cap)
        self.http_port = http_port
        self.http_host = http_host
        self.drain_s = drain_s
        self.ready_queue_depth = ready_queue_depth
        # throughput data plane (PR 3): adaptive batcher ceiling (None =
        # batch_size, i.e. the pre-PR-3 fixed read) + coalescing budget,
        # preprocess fan-out, device pipeline depth, amortized trim period.
        # inflight_batches bounds the dispatched-handle QUEUE between the
        # predict and write workers; up to two more batches are transiently
        # resident (one mid-readback in the writer, one held by the predict
        # worker awaiting a slot) — size device memory for inflight + 2
        self.max_batch = max_batch
        self.max_wait_ms = max_wait_ms
        self.preprocess_workers = preprocess_workers
        self.inflight_batches = inflight_batches
        self.trim_interval_s = trim_interval_s
        # per-record span recording (PR 4).  On by default — the ring buffer
        # is bounded — but the span dicts + tracer lock are per-record hot-
        # path cost, so latency-critical deployments can switch it off
        # (metrics histograms stay on; only traces go dark)
        self.tracing = bool(tracing)
        # horizontal replicas (PR 5): stable identity for this engine (None
        # = derived from pid), how long a claimed record may sit idle before
        # another replica may reclaim it (must exceed the worst-case single-
        # record service time; <= 0 disables reclaiming), and how often the
        # reclaim sweep runs (None = lease_s / 2)
        self.replica_id = replica_id
        self.lease_s = lease_s
        self.reclaim_interval_s = reclaim_interval_s
        # poison-pill parking (PR 10): a record delivered more than this
        # many times (first delivery counts) is parked to the dead-letter
        # queue with a `max-deliveries-exceeded` error instead of looping
        # through reclaim -> crash -> reclaim forever.  <= 0 disables.
        self.max_deliveries = int(max_deliveries)
        # sharded multi-chip serving (PR 6): route predict through a pjit'd
        # program over the ICI mesh.  `sharding`: off (single-chip, the
        # default) | auto (batch-shard small models, tensor-shard large) |
        # batch | tensor.  `mesh_shape`: None = all devices, int N = first
        # N, or a (data, model) tuple for hybrid layouts.
        self.mesh_shape = mesh_shape
        self.sharding = str(sharding or "off")
        # ingestion gateway (PR 7): serve POST /v1/enqueue + GET /v1/result
        # on the probe port.  Off = probe-only port (deployments that front
        # ingest elsewhere)
        self.gateway = bool(gateway)
        # zero cold start (PR 11).  `warmup`: AOT-compile the full
        # (bucket, scales-variant) program set at start() — False (off,
        # the pre-PR-11 behaviour), True (input spec inferred from the
        # topology's declared input shape), or a spec dict
        # {"shape": [d0, ...], "dtype": "<f4", "scales": "auto|both|off",
        #  "max_batch": N} for models that declare nothing.  /readyz
        # reports `warming (k/n programs)` until the set is compiled.
        # `compile_cache_dir`: persistent XLA compilation cache directory
        # (resolved by `aot.compile_cache_dir`: $JAX_COMPILATION_CACHE_DIR
        # wins over it, unset means the fixed in-checkout default, "off"
        # disables) — the second replica of a topology loads executables
        # from disk instead of compiling.
        self.warmup = warmup if isinstance(warmup, dict) else bool(warmup)
        self.compile_cache_dir = compile_cache_dir
        # continuous batching (PR 12).  `generation`: None (off, the
        # batch-in/batch-out predict plane) | True (defaults) | a config
        # dict — see serving/generate.GenerationParams for the keys
        # (max_active_slots, max_tokens, eos_id, start_id, max_prompt_len,
        # bucket_lens, prefill_buckets, stream_interval).  When set, the
        # predict+write stages are replaced by the token-level scheduler:
        # requests join/leave the in-flight decode batch at step
        # boundaries, results stream through OutputQueue partials, and the
        # model must expose init_decode/decode_step.
        self.generation = generation if isinstance(generation, dict) \
            else ({} if generation else None)
        # fleet-wide distributed tracing (PR 13).  `trace_sample`: HEAD
        # sampling rate in [0, 1] — the keep/drop verdict is a pure
        # function of the trace_id (common/observability.trace_sampled),
        # so the LB, gateway and every replica agree without coordination.
        # Generation workloads emit per-boundary decode spans, so the
        # sampling knob exists BEFORE per-token span volume does.  Error
        # spans (quarantine/shed) are always recorded regardless of rate.
        try:
            self.trace_sample = min(max(float(trace_sample), 0.0), 1.0)
        except (TypeError, ValueError):
            self.trace_sample = 1.0
        # SLO attribution (PR 13): {"latency_ms": 500, "window_s": 60,
        # "target": 0.99} drives serving_slo_violations_total{stage=} and
        # the serving_slo_burn_rate gauge.  None = off.
        self.serving_slo = serving_slo if isinstance(serving_slo, dict) \
            else None
        # fused-dequant quantized predict (PR 14).  `quantize`: None/off
        # (float serve, the default) | "int8"/8 | "int4"/4 | a config dict
        # {"bits": 8|4, "group_size": 64, "percentile": 99.9,
        #  "calib": "/path/to/batch.npy|.npz"} — applied at ClusterServing
        # construction (before sharding) when the model is not already
        # quantized.  int4 is weight-only (no calibration needed); int8
        # needs activation scales, so an unquantized model REQUIRES the
        # `calib` file (fail-fast at construction, like a bad mesh) —
        # calibrate offline with do_quantize(FeatureSet) for real data, or
        # let `manager warmup` quantize + export the mmap store so replica
        # forks serve quantized without re-quantizing.
        self.quantize = resolve_quantize_spec(quantize)
        # incident flight recorder (PR 15).  `flight_recorder`: record
        # typed events (state transitions, retunes, reclaims, quarantines,
        # sheds, warm-up phases, scheduler boundaries) into the bounded
        # process ring that `manager incident` bundles — per-EVENT cost is
        # one dict + deque append, so it stays on by default; off compiles
        # the hop down to a no-op like tracing=False.  `recorder_ring`
        # re-bounds the ring (default 4096 events); size it to cover the
        # diagnosis window between manager drains (1 s) at your event
        # rate.  `profiling`: serve POST /debug/profile?seconds=N on the
        # probe port (jax.profiler trace into the deployment dir) — probe
        # surface only, the LB never proxies /debug; false removes the
        # route entirely.
        self.flight_recorder = bool(flight_recorder)
        self.recorder_ring = (None if recorder_ring is None
                              else max(16, int(recorder_ring)))
        self.profiling = bool(profiling)
        # zero-drop rollout (PR 16).  `model_version`: the registry
        # version this replica serves — normally injected by the
        # supervisor's spawn spec, not set in config.yaml.  Rides the
        # health doc, /healthz and every result payload so a mixed-version
        # fleet mid-rollout is observable end to end.  `faults`:
        # deterministic fault-injection points gated on model_version
        # (serving/faults.py) — strictly opt-in chaos for rollout tests
        # and the `serving_bench --rollout` A/B; None (the default) wires
        # nothing into the hot path.
        self.model_version = (None if model_version is None
                              else str(model_version))
        self.faults = faults if isinstance(faults, dict) else None
        # overload armor (PR 17).  `admission`: tenant-aware token-bucket
        # admission at the gateway trust edge (serving/admission.py —
        # enabled, rate, burst, tenants, depth_fractions); None = the
        # pre-PR-17 fleet-wide max_depth 429 only.  `brownout`: the
        # hysteresis degradation ladder driven by the SLO burn rate
        # (serving/brownout.py — enter, exit_ratio, dwell_s, hold_s,
        # batch_max_tokens); needs `serving_slo` for its input signal.
        self.admission = admission if isinstance(admission, dict) else None
        self.brownout = brownout if isinstance(brownout, dict) else None
        # usage metering & attribution (PR 19).  `metering`: None/True =
        # on with defaults ({tenant=,model=} labelled series, per-interval
        # usage journal deltas drained by the manager, per-tenant SLO
        # views); a dict configures it ({"enabled": bool, "max_tenants":
        # N, "slo_objectives": {tenant: {latency_ms, ...}}}); False turns
        # the labelled surface off (the pre-PR-19 unlabelled series — the
        # metering-off arm of `serving_bench --metering-overhead`).
        if isinstance(metering, dict):
            self.metering = metering
        elif metering is None:
            self.metering = {}
        else:
            self.metering = {} if metering else {"enabled": False}

    @classmethod
    def from_dict(cls, p: Dict) -> "ServingParams":
        """The one params-dict parser (config.yaml `params:` section) —
        manager.serving_params and from_yaml both delegate here so no
        surface silently drops keys."""
        return cls(
            batch_size=int(p.get("batch_size", 4)),
            top_n=int(p.get("top_n", 5)),
            poll_timeout_s=float(p.get("poll_timeout_s", 0.05)),
            stream_max_len=int(p.get("stream_max_len", 100000)),
            filter_threshold=p.get("filter_threshold"),
            write_retries=int(p.get("write_retries", 5)),
            write_backoff_s=float(p.get("write_backoff_s", 0.05)),
            pipeline_depth=int(p.get("pipeline_depth", 2)),
            max_worker_restarts=int(p.get("max_worker_restarts", 5)),
            worker_backoff_s=float(p.get("worker_backoff_s", 0.05)),
            breaker_threshold=int(p.get("breaker_threshold", 5)),
            breaker_cooldown_s=float(p.get("breaker_cooldown_s", 0.5)),
            http_port=(None if p.get("http_port") is None
                       else int(p["http_port"])),
            http_host=str(p.get("http_host", "127.0.0.1")),
            drain_s=(None if p.get("drain_s") is None
                     else float(p["drain_s"])),
            ready_queue_depth=(None if p.get("ready_queue_depth") is None
                               else int(p["ready_queue_depth"])),
            max_batch=(None if p.get("max_batch") is None
                       else int(p["max_batch"])),
            max_wait_ms=float(p.get("max_wait_ms", 5.0)),
            preprocess_workers=int(p.get("preprocess_workers", 1)),
            inflight_batches=int(p.get("inflight_batches", 2)),
            trim_interval_s=float(p.get("trim_interval_s", 5.0)),
            tracing=bool(p.get("tracing", True)),
            replica_id=(None if p.get("replica_id") is None
                        else str(p["replica_id"])),
            lease_s=float(p.get("lease_s", 30.0)),
            reclaim_interval_s=(None if p.get("reclaim_interval_s") is None
                                else float(p["reclaim_interval_s"])),
            max_deliveries=int(p.get("max_deliveries", 5)),
            mesh_shape=(None if p.get("mesh_shape") is None
                        else tuple(int(v) for v in p["mesh_shape"])
                        if isinstance(p["mesh_shape"], (list, tuple))
                        else int(p["mesh_shape"])),
            sharding=str(p.get("sharding", "off")),
            gateway=bool(p.get("gateway", True)),
            warmup=p.get("warmup", False),
            compile_cache_dir=p.get("compile_cache_dir"),
            generation=p.get("generation"),
            trace_sample=p.get("trace_sample", 1.0),
            serving_slo=p.get("serving_slo"),
            quantize=p.get("quantize"),
            flight_recorder=bool(p.get("flight_recorder", True)),
            recorder_ring=(None if p.get("recorder_ring") is None
                           else int(p["recorder_ring"])),
            profiling=bool(p.get("profiling", True)),
            model_version=p.get("model_version"),
            faults=p.get("faults"),
            admission=p.get("admission"),
            brownout=p.get("brownout"),
            metering=p.get("metering"))

    @staticmethod
    def from_yaml(path: str) -> "ServingParams":
        import yaml
        with open(path) as f:
            cfg = yaml.safe_load(f) or {}
        return ServingParams.from_dict(cfg.get("params", {}))


class ClusterServing:
    def __init__(self, model: InferenceModel, queue: BaseQueue,
                 params: Optional[ServingParams] = None,
                 preprocess: Callable = default_preprocess,
                 postprocess: Optional[Callable] = None,
                 tensorboard_dir: Optional[str] = None,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        self.model = model
        self.queue = queue
        self.params = params or ServingParams()
        # fused-dequant quantized predict (PR 14): quantize BEFORE the
        # mesh placement so the quantized leaves are what the plan shards
        # — a bad spec (int8 with no calibration) fails construction, not
        # a mid-stream request.  A model restored from a quantized weight
        # store skips this (already quantized).
        if self.params.quantize and isinstance(model, InferenceModel):
            apply_quantize(model, self.params.quantize)
        self._qbits: Optional[int] = None    # lazily cached health() value
        # sharded multi-chip serving (PR 6): place the model over the mesh
        # BEFORE any worker can dispatch — a bad mesh config fails
        # construction, not a mid-stream request.  Idempotent for a model
        # shared across engines (bench --replicas).
        if self.params.sharding != "off" and isinstance(model, InferenceModel):
            model.shard(mesh=self.params.mesh_shape,
                        sharding=self.params.sharding)
        self.preprocess = preprocess
        self.postprocess = postprocess or (
            lambda p: default_postprocess(p, self.params.top_n))
        self._stop = threading.Event()
        self._draining = threading.Event()   # graceful drain in progress
        # decommission drain (PR 10): this replica stops CLAIMING new work
        # and flushes what it holds, while the shared queue stays open for
        # the surviving replicas — the scale-down shape.  The PR 2 whole-
        # deployment drain (admission closed) is the close_admission=True
        # path of shutdown().
        self._retiring = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.total_records = 0
        self.dead_lettered = 0
        self.shed = 0                        # deadline-exceeded rejections
        # horizontal replicas (PR 5): identity + reclaim/redelivery state
        self.replica_id = self.params.replica_id or \
            f"replica-{os.getpid()}-{new_trace_id()[:6]}"
        self.reclaimed = 0                   # orphans re-claimed by us
        self.duplicates = 0                  # redeliveries suppressed
        self._last_reclaim = 0.0             # monotonic; 0 = sweep at start
        self._redelivered: Dict[str, int] = {}   # rid -> delivery count
        # rid -> monotonic claim ts for records currently in OUR pipeline:
        # the reclaim sweep must not treat its own slow in-flight work (a
        # cold jit compile, a long batch) as another replica's orphans —
        # self-reclaim would double-serve them.  Entries clear on ack.
        self._inflight: Dict[str, float] = {}
        self._hb_ts = time.monotonic()       # read-loop heartbeat stamp
        # zero cold start (PR 11): AOT warm-up progress (published on
        # /readyz + the health doc) and the construction-to-capable clock
        # the cold-start metric reports, read off this engine's start-up
        # marks (PR 37; ``_capable``)
        self.startup = StartupMarks(get_startup().snapshot())
        self.startup.stamp("engine")
        self._cold_start_s: Optional[float] = None
        self._awaits_first_result = True
        self._warm_state: Dict = {"state": "off", "total": 0,
                                  "compiled": 0, "failed": 0,
                                  "seconds": None}
        self._warm_thread: Optional[threading.Thread] = None
        # the queue handle's claims are made under our replica identity
        try:
            self.queue.consumer = self.replica_id
        except Exception:  # noqa: BLE001 — exotic custom backend
            pass
        self._http = None                    # HealthServer when http_port set
        # unified telemetry (PR 4): per-ENGINE registry by default so
        # counters and stage percentiles stay attributable when several
        # engines share a process (tests, embedded serving); pass
        # observability.get_registry() to pool process-wide
        self.registry = registry or MetricsRegistry()
        self.tracer = tracer or Tracer()
        # fleet tracing (PR 13): every span this replica records names it,
        # so the fleet-merged timeline attributes work per process
        if self.tracer.replica_id is None:
            self.tracer.replica_id = self.replica_id
        # per-trace propagated context: trace_id -> (parent span id,
        # sampled flag) parsed from the record's trace_ctx at read.  The
        # span wrapper consults it so EVERY stage span parents under the
        # gateway/LB span without threading context through the pipeline
        # tuples.  Bounded (trimmed oldest-half past the cap).
        self._trace_meta: Dict[str, Tuple[Optional[str], bool]] = {}
        # rid -> queue-wait seconds measured at claim (SLO attribution)
        self._qwait: Dict[str, float] = {}
        # span recording is per-record hot-path work; params.tracing=False
        # compiles the switch down to a no-op callable.  With tracing on,
        # the wrapper applies head sampling (pure function of trace_id —
        # fleet-consistent) and the parent lookup; error spans always
        # record so a sampled-out poisoned record stays diagnosable.
        self._span = (self._record_span if self.params.tracing
                      else (lambda *a, **kw: None))
        # SLO attribution (PR 13): judge each completed record against the
        # configured latency objective, charging the dominant stage
        self._slo = SloTracker.from_config(self.registry,
                                           self.params.serving_slo)
        # incident flight recorder (PR 15): the PROCESS ring — one per
        # process by design, so the AOT compile listeners, the gateway
        # and the engine all land on the one timeline the manager drains
        # to <pidfile>.events.jsonl.  Events carry this replica's id so
        # several engines sharing a test process stay attributable.
        # flight_recorder=False compiles the hop to a no-op (the ring
        # itself stays — other subsystems may still record).
        from analytics_zoo_tpu.common.observability import get_recorder
        self.recorder = get_recorder()
        if self.params.recorder_ring:
            self.recorder.resize(self.params.recorder_ring)
        self._event = (self._record_event if self.params.flight_recorder
                       else (lambda *a, **kw: None))
        # zero-drop rollout (PR 16): version identity + fault injection.
        # The injector is built even when inert (describe() rides the
        # health doc), but fault points only wire into the hot path when
        # armed for THIS replica's version — a predict fault instance-
        # patches do_predict, which `_dispatch_batch`'s custom-predict
        # fallback keeps on the real quarantine/bisect path.
        from analytics_zoo_tpu.serving.faults import FaultInjector
        self.model_version = self.params.model_version
        self._faults = FaultInjector(self.params.faults,
                                     self.model_version)
        if self._faults.predict_active and \
                isinstance(model, InferenceModel):
            model.do_predict = self._faults.wrap_predict(model.do_predict)
        # overload armor (PR 17): the brownout degradation ladder (driven
        # by the SLO burn rate the read loop feeds it) and the tenant-
        # aware admission gate the gateway consults per request.  Both
        # are config-gated — None wires nothing into the hot path.
        self._brownout = None
        self._brownout_next = 0.0            # next ladder tick (throttled)
        if self.params.brownout is not None:
            from analytics_zoo_tpu.serving.brownout import BrownoutLadder
            self._brownout = BrownoutLadder(
                self.params.brownout,
                recorder=(self.recorder if self.params.flight_recorder
                          else None),
                registry=self.registry, replica_id=self.replica_id)
        self._admission = None
        if self.params.admission is not None:
            from analytics_zoo_tpu.serving.admission import (
                AdmissionController)
            self._admission = AdmissionController(
                self.params.admission, registry=self.registry,
                queue_depth_fn=self._admission_depth,
                max_depth=getattr(queue, "max_depth", None),
                brownout_stage_fn=(lambda: self.brownout_stage),
                faults=self._faults)
        # smoothed per-batch predict service time — the early-drop gate's
        # "can this record still make its deadline" estimate (None until
        # the first batch lands: never drop on a guess)
        self._predict_ewma_s: Optional[float] = None
        # scheduler-side armor (priority-ordered claim/shed + deadline
        # early drop) rides the same opt-in as the config blocks, so a
        # deployment without them keeps the exact pre-PR-17 claim path
        self._armor = (self.params.admission is not None
                       or self.params.brownout is not None)
        # on-demand device profiling (PR 15): one jax.profiler trace at a
        # time, written under profile_dir (the manager points it at
        # <pidfile>.profiles)
        self.profile_dir: Optional[str] = None
        self._profile_lock = threading.Lock()
        self._profile_active = False
        self._t_start = time.monotonic()     # re-stamped by start()
        self._snapshot_seq = itertools.count(1)
        p = self.params
        self._write_retry = RetryPolicy(max_retries=p.write_retries,
                                        base_delay_s=p.write_backoff_s)
        self._breaker = CircuitBreaker(failure_threshold=p.breaker_threshold,
                                       cooldown_s=p.breaker_cooldown_s,
                                       name="result-write")
        # separate breaker for dead-letter writes: sharing the result-write
        # breaker would let a succeeding put_error reset the put_result
        # failure streak (and vice versa) — with the store fully down, this
        # one trips too and bounds the per-record cost of quarantining
        self._dead_breaker = CircuitBreaker(
            failure_threshold=p.breaker_threshold,
            cooldown_s=p.breaker_cooldown_s, name="dead-letter-write")
        self._pre_sup: Optional[SupervisedThread] = None
        self._predict_sup: Optional[SupervisedThread] = None
        self._write_sup: Optional[SupervisedThread] = None
        self._pre_pool = None                # lazy preprocess thread pool
        self._pre_pool_size = 0              # workers in the live pool
        # live retune (PR 10 autoscaler): validated knob targets staged by
        # retune() and APPLIED at the preprocess loop's batch boundary —
        # the one thread that owns the batcher/pool — so a mid-batch nudge
        # can never tear the pipeline
        self._knob_lock = threading.Lock()
        self._pending_knobs: Dict[str, float] = {}
        self._last_trim = time.monotonic()   # amortized trim schedule
        # per-stage timers + end-to-end (read -> result written) latency,
        # now registry histograms: same .record()/.snapshot() surface as the
        # old StageStats, plus Prometheus exposition
        reg = self.registry
        stage_hist = reg.histogram(
            "serving_stage_seconds",
            "Per-stage latency of the serving pipeline", labels=("stage",))
        self._stages = {
            name: stage_hist.labels(stage=name) for name in
            ("read", "preprocess", "stage_wait", "predict", "write")}
        self._e2e = reg.histogram(
            "serving_e2e_seconds",
            "Per-record latency from read_batch return to result written")
        # usage metering & attribution (PR 19): the meter owns the
        # {tenant=,model=} labelled series (serving_records_total,
        # serving_generated_tokens_total, serving_sheds_total,
        # serving_device_seconds_total, serving_request_seconds), the
        # per-interval usage-journal deltas the manager drains next to
        # spans/events, and the per-tenant SLO burn views.  With
        # metering {"enabled": False} it registers the pre-PR-19
        # unlabelled records/tokens series instead (the off arm of
        # `serving_bench --metering-overhead`).
        from analytics_zoo_tpu.serving.metering import UsageMeter
        _adm_tenants = ()
        if isinstance(self.params.admission, dict) and \
                isinstance(self.params.admission.get("tenants"), dict):
            _adm_tenants = tuple(self.params.admission["tenants"])
        self.meter = UsageMeter(
            reg, model=self.model_version,
            cfg=self.params.metering,
            tenants_configured=_adm_tenants,
            slo_defaults=self.params.serving_slo)
        self._m_quarantined = reg.counter(
            "serving_quarantined_total", "Records dead-lettered, by stage",
            labels=("stage",))
        self._m_shed = reg.counter(
            "serving_shed_total", "Deadline-exceeded records shed")
        # binary wire telemetry (PR 7): bytes observed per record format,
        # materialized at zero so mixed-traffic dashboards see every series
        # from day one, plus a per-record decode histogram labeled by
        # format so mixed-traffic decode cost is attributable (the
        # aggregate serving_stage_seconds{stage="preprocess"} document is
        # unchanged for PR 3/4 consumers)
        self._m_wire_bytes = reg.counter(
            "serving_wire_bytes_total",
            "Wire bytes observed at read, by record format",
            labels=("format",))
        for fmt in (_wire.FMT_JSON, _wire.FMT_BIN, _wire.FMT_SHM):
            self._m_wire_bytes.labels(format=fmt).inc(0)
        self._pre_fmt_hist = reg.histogram(
            "serving_preprocess_seconds",
            "Per-record preprocess (decode) latency, by wire format",
            labels=("format",))
        # replica telemetry (PR 5), materialized at zero so the series are
        # scrapeable from day one, not only after the first failover
        self._m_reclaimed = reg.counter(
            "serving_reclaimed_total",
            "Orphaned records re-claimed from dead replicas, by backend",
            labels=("backend",)).labels(backend=type(queue).__name__)
        self._m_reclaimed.inc(0)
        self._m_duplicates = reg.counter(
            "serving_duplicate_results_total",
            "Redelivered records suppressed because a result already "
            "existed")
        self._m_duplicates.inc(0)
        self._hb_gauge = reg.gauge(
            "serving_heartbeat_age_seconds",
            "Seconds since this replica's read loop last made progress",
            labels=("replica",))
        self._hb_gauge.labels(replica=self.replica_id).set_function(
            self._heartbeat_age)
        # callback gauges are registered additively (engines pooling into
        # one registry each contribute to the sum) and deregistered on
        # shutdown so a stopped engine neither skews the scrape nor stays
        # reachable from a shared registry
        self._gauge_fns = [
            (reg.gauge("serving_queue_depth", "Records waiting in the stream",
                       fn=self._queue_depth_metric), self._queue_depth_metric),
            (reg.gauge("serving_dead_letters", "Dead-letter backlog",
                       fn=self._dead_letter_metric), self._dead_letter_metric),
            (reg.gauge("serving_worker_restarts",
                       "Supervised-worker restarts across all stages",
                       fn=self._restarts_metric), self._restarts_metric),
        ]
        trips = lambda: self._breaker.trip_count  # noqa: E731
        self._gauge_fns.append(
            (reg.gauge("serving_breaker_trips", "Result-write breaker trips",
                       fn=trips), trips))
        # cold-start observability (PR 11): how long this replica took to
        # become useful, split into its phases — `load` is the model's
        # weight-load wall (stamped by do_load*; mmap'd store loads are
        # near-zero), `compile` the AOT warm-up pass.  The autoscaler reads
        # these off the health doc to log scale-up actuation lag.
        self._g_warm = reg.gauge(
            "serving_warmup_seconds",
            "Replica warm-up wall seconds, by phase", labels=("phase",))
        self._g_cold = reg.gauge(
            "replica_cold_start_seconds",
            "Engine construction to first result written, this replica")
        load_s = getattr(model, "load_seconds", None)
        if load_s is not None:
            self._g_warm.labels(phase="load").set(float(load_s))
        # inference-side latency/batch histograms (InferenceModel) ride this
        # engine's registry so one scrape covers the whole data plane (see
        # InferenceModel.bind_registry for the re-binding/pinning rules)
        if isinstance(model, InferenceModel):
            model.bind_registry(self.registry)
        # continuous batching (PR 12): the token-level scheduler replaces
        # the predict+write stages when `params.generation` is set.  Built
        # at construction so a model lacking the step-wise decode API
        # fails fast, not mid-stream.
        self._batcher = None
        self._gen_params = None
        if self.params.generation is not None:
            from analytics_zoo_tpu.serving.generate import (
                ContinuousBatcher, GenerationParams)
            self._gen_params = GenerationParams.from_dict(
                self.params.generation)
            self._batcher = ContinuousBatcher(model, self._gen_params)
            self._batcher.startup = self.startup
            self._m_decode_steps = reg.counter(
                "serving_decode_steps_total",
                "Decode-step boundaries executed by the token scheduler")
            self._m_decode_steps.inc(0)
            self._m_ttft = reg.histogram(
                "serving_time_to_first_token_seconds",
                "Request admission to first generated token")
            self._g_tps = reg.gauge(
                "serving_tokens_per_second",
                "Generated tokens per second over the last rate window")
            self._g_tps.set(0.0)       # materialized: scrapable pre-traffic
            slots_fn = (lambda b=self._batcher: float(b.active))
            self._gauge_fns.append(
                (reg.gauge("serving_active_slots",
                           "Decode slots currently serving a request",
                           fn=slots_fn), slots_fn))
            # where the generate loop's time goes (PR 25): the batcher's
            # phase clock read at scrape time, no second accumulation
            phase_g = reg.gauge(
                "serving_generate_phase_seconds_total",
                "Wall time of the generate thread by phase of its cycle "
                "(the phases partition it; the device is busy in "
                "prefill_wait and decode_wait)", labels=("phase",))
            for name in self._batcher.clock.phases:
                fn = (lambda n=name, b=self._batcher:
                      b.clock.totals()[0][n])
                child = phase_g.labels(phase=name)
                child.add_function(fn)
                self._gauge_fns.append((child, fn))
            # what the model's programs count on the device (PR 32): the
            # scheduler's last reading, none for a model without counters
            if self._batcher.model_counters:
                model_g = reg.gauge(
                    "serving_generate_model_counter",
                    "Cumulative device-side counters of the served model's "
                    "programs (the paged contract's paged_counters), as "
                    "the scheduler last read them", labels=("name",))
                for name in self._batcher.model_counters:
                    fn = (lambda n=name, b=self._batcher:
                          float(b.model_counters[n]))
                    child = model_g.labels(name=name)
                    child.add_function(fn)
                    self._gauge_fns.append((child, fn))
            self._last_steps = 0
            self._tps_window = (time.monotonic(), 0)   # (t0, tokens0)
            # generation continuity (PR 20): where checkpoints spool
            # (set post-construction by the manager, like profile_dir —
            # None disables checkpointing even with an interval set) and
            # the resume counters, materialized at zero so the chaos
            # acceptance can assert exact deltas
            self.snapshot_path = None
            self._last_resumed = 0
            self._m_resumed = reg.counter(
                "serving_generations_resumed_total",
                "Generations resumed from a dead owner's checkpoint")
            self._m_resumed.inc(0)
            self._m_resume_wasted = reg.counter(
                "serving_resume_wasted_tokens_total",
                "Generated tokens re-computed because a generation "
                "restarted without (or beyond) a usable checkpoint")
            self._m_resume_wasted.inc(0)
            # paged KV pool (PR 18): occupancy / free-block / prefix-hit
            # gauges so admission stalls are visible before the typed
            # kv_pool_exhausted flight-recorder event fires
            pool = getattr(self._batcher, "_pool", None)
            if pool is not None:
                free_fn = (lambda p=pool: float(p.free_blocks))
                self._gauge_fns.append(
                    (reg.gauge("serving_kv_pool_free_blocks",
                               "Free blocks in the paged KV pool",
                               fn=free_fn), free_fn))
                occ_fn = (lambda p=pool:
                          float(p.used_blocks) / max(1, p.n_blocks))
                self._gauge_fns.append(
                    (reg.gauge("serving_kv_pool_occupancy",
                               "Used fraction of the paged KV pool",
                               fn=occ_fn), occ_fn))
                prefix = getattr(self._batcher, "_prefix", None)
                if prefix is not None:
                    hits_fn = (lambda x=prefix: float(x.hits))
                    self._gauge_fns.append(
                        (reg.gauge("serving_kv_prefix_hits_total",
                                   "Prefix-cache hits at admission",
                                   fn=hits_fn), hits_fn))
        # resource accounting (PR 15): decompose device memory into
        # weights (PR 14 stored-dtype bytes) / kv_state (PR 12 lane
        # buffers) / executables (PR 11 AOT cache) — live gauges + the
        # health doc `resources` block the fleet aggregation sums
        from analytics_zoo_tpu.inference.resources import ResourceLedger
        from analytics_zoo_tpu.common.observability import process_stats
        self._ledger = ResourceLedger(model, batcher=self._batcher)
        hbm = reg.gauge("serving_hbm_bytes",
                        "Device memory by component: weights (stored "
                        "dtype), kv_state (generation lane buffers), "
                        "executables (AOT generated code)",
                        labels=("component",))
        for comp in ResourceLedger.COMPONENTS:
            fn = (lambda c=comp: self._ledger.hbm_bytes(c))
            child = hbm.labels(component=comp)
            child.add_function(fn)
            self._gauge_fns.append((child, fn))
        # per-process resource gauges (PR 15 satellite): RSS / CPU / FDs /
        # threads — per PROCESS, so engines pooling one registry in a
        # test process sum to the same process figure N times; real
        # deployments run one engine per process and the fleet merge sums
        # across processes
        for name, help_, key in (
                ("process_resident_memory_bytes",
                 "Resident set size of this serving process", "rss_bytes"),
                ("process_cpu_seconds_total",
                 "User+system CPU seconds consumed by this process",
                 "cpu_seconds"),
                ("process_open_fds",
                 "Open file descriptors in this process", "open_fds"),
                ("process_threads_total",
                 "Live threads in this process", "threads")):
            fn = (lambda k=key: float(process_stats().get(k) or 0))
            g = reg.gauge(name, help_, fn=fn)
            self._gauge_fns.append((g, fn))
        self._tb = None
        if tensorboard_dir:
            from analytics_zoo_tpu.utils.tbwriter import FileWriter
            self._tb = FileWriter(tensorboard_dir)

    # -- callback-gauge samplers (guarded: a dead backend yields NaN) --------
    def _queue_depth_metric(self) -> float:
        try:
            return float(self.queue.depth())
        except Exception:  # noqa: BLE001 — backend down
            return float("nan")

    def _dead_letter_metric(self) -> float:
        try:
            return float(self.queue.dead_letter_count())
        except Exception:  # noqa: BLE001
            return float("nan")

    def _restarts_metric(self) -> float:
        return float(sum(
            s.health()["restart_count"]
            for s in (self._pre_sup, self._predict_sup, self._write_sup)
            if s is not None))

    def _heartbeat_age(self) -> float:
        return time.monotonic() - self._hb_ts

    # -- overload armor (PR 17) ----------------------------------------------
    def _admission_depth(self) -> Optional[int]:
        """Queue depth for the admission gate's class caps; None (no
        signal, admit) when the backend is unreachable — a dead backend
        is the breaker's problem, not a reason to 429."""
        try:
            return int(self.queue.depth())
        except Exception:  # noqa: BLE001 — backend down
            return None

    @property
    def brownout_stage(self) -> int:
        return self._brownout.stage if self._brownout is not None else 0

    def admit_record(self, tenant=None, priority=None):
        """The gateway's per-request admission consult.  Returns an
        ``admission.Decision``, or None when no controller is configured
        (the gateway falls through to the legacy fleet-wide 429)."""
        if self._admission is None:
            return None
        d = self._admission.admit(tenant, priority)
        if not d.admitted:
            # rejections belong on the incident timeline next to the
            # brownout transitions they usually accompany
            self._event("admission_reject", reason=d.reason,
                        tenant=d.tenant, priority=d.priority)
        return d

    def _brownout_tick(self) -> None:
        """Feed the ladder the current SLO burn rate (throttled to 4 Hz —
        the ladder's dwell/hold windows are seconds, per-claim sampling
        would only add gauge reads to the hot loop)."""
        if self._brownout is None or self._slo is None:
            return
        now = time.monotonic()
        if now < self._brownout_next:
            return
        self._brownout_next = now + 0.25
        try:
            burn = self._slo.snapshot().get("burn_rate", 0.0)
        except Exception:  # noqa: BLE001 — ladder input, not load-bearing
            return
        self._brownout.observe(burn, now)

    def _note_predict_time(self, seconds: float) -> None:
        """EWMA of per-batch predict wall time (alpha 0.2) — the early
        drop gate's service-time estimate."""
        if seconds <= 0:
            return
        prev = self._predict_ewma_s
        self._predict_ewma_s = seconds if prev is None \
            else 0.8 * prev + 0.2 * seconds

    def _pressure_level(self) -> int:
        """Engine-side shed aggressiveness (0/1/2) from the staged-buffer
        backlog, the queue-depth fraction, and the brownout stage — see
        ``admission.pressure_level``."""
        from analytics_zoo_tpu.serving.admission import pressure_level
        staged = getattr(self, "_staged", None)
        staged_frac = 0.0
        if staged is not None:
            cap = max(1, staged.maxsize or 1)
            staged_frac = staged.qsize() / cap
        depth_frac = 0.0
        max_depth = getattr(self.queue, "max_depth", None)
        if max_depth:
            depth = self._admission_depth()
            if depth is not None:
                depth_frac = depth / float(max_depth)
        return pressure_level(staged_frac, depth_frac, self.brownout_stage)

    # -- incident flight recorder (PR 15) ------------------------------------
    def _record_event(self, kind: str, **attrs) -> None:
        """The engine's event hop: stamp replica identity, never raise —
        forensics must not be able to take serving down."""
        try:
            self.recorder.record(kind, replica=self.replica_id, **attrs)
        except Exception:  # noqa: BLE001 — diagnostic, not load-bearing
            pass

    # -- on-demand device profiling (PR 15) ----------------------------------
    PROFILE_MIN_S, PROFILE_MAX_S = 0.05, 300.0

    def start_profile(self, seconds: float,
                      out_dir: Optional[str] = None) -> Dict:
        """Arm one ``jax.profiler`` trace for ``seconds`` into the
        deployment's profile dir (the manager points ``profile_dir`` at
        ``<pidfile>.profiles``).  ONE trace at a time — a second request
        while one is armed raises ``RuntimeError`` (the gateway maps it
        to 409).  The start/sleep/stop cycle runs entirely on a daemon
        thread: ``jax.profiler.start_trace`` can take SECONDS to bring
        the profiler server up (measured ~15 s in sandboxed containers),
        and a probe-port handler must never block that long — the 202
        reply means "armed", the trace lands in ``path`` when done (the
        ``profile_done`` flight-recorder event marks completion)."""
        import tempfile
        seconds = min(max(float(seconds), self.PROFILE_MIN_S),
                      self.PROFILE_MAX_S)
        base = out_dir or self.profile_dir or os.path.join(
            tempfile.gettempdir(), f"serving-profile-{self.replica_id}")
        path = os.path.join(
            base, time.strftime("%Y%m%d-%H%M%S") + f"-{os.getpid()}")
        with self._profile_lock:
            if self._profile_active:
                raise RuntimeError(
                    "a profiling trace is already armed/running — one "
                    "at a time per process")
            os.makedirs(path, exist_ok=True)
            self._profile_active = True

        def _run():
            try:
                import jax
                jax.profiler.start_trace(path)
                time.sleep(seconds)
                jax.profiler.stop_trace()
                self._event("profile_done", path=path, seconds=seconds)
            except Exception as e:  # noqa: BLE001 — the trace failing
                # must not leave the engine permanently "busy"
                logger.exception("serving: profiling trace failed")
                self._event("profile_error",
                            error=f"{type(e).__name__}: {e}"[:200])
                try:
                    import jax
                    jax.profiler.stop_trace()
                except Exception:  # noqa: BLE001 — was never started
                    pass
            finally:
                with self._profile_lock:
                    self._profile_active = False

        threading.Thread(target=_run, name="serving-profile",
                         daemon=True).start()
        self._event("profile_start", path=path, seconds=seconds)
        logger.info("serving: profiling armed for %.2fs into %s",
                    seconds, path)
        return {"profiling": True, "path": path,
                "seconds": seconds, "replica_id": self.replica_id}

    # -- distributed tracing (PR 13) -----------------------------------------
    _TRACE_META_CAP = 8192

    def _record_span(self, stage, t0, t1, trace_id=None, uri=None,
                     error=None, parent_id=None, attrs=None):
        """The engine's span hop: head sampling + cross-process parenting.
        Error spans bypass sampling — a quarantine in a sampled-out trace
        must still be diagnosable (and lands in the tracer's error side
        buffer either way)."""
        meta = self._trace_meta.get(trace_id) if trace_id else None
        if error is None:
            if meta is not None:
                if not meta[1]:
                    return None
            elif not trace_sampled(trace_id, self.params.trace_sample):
                return None
        if parent_id is None and meta is not None:
            parent_id = meta[0]
        return self.tracer.span(stage, t0, t1, trace_id=trace_id, uri=uri,
                                error=error, parent_id=parent_id,
                                attrs=attrs)

    def _note_trace_ctx(self, rid, rec: Dict, t_claim: float) -> None:
        """Fold a record's propagated ``trace_ctx`` into this replica:
        remember (parent span id, sampled) for the span wrapper, and
        record the QUEUE-WAIT span — gateway/client ingest to this claim,
        measured as one wall-clock delta so no cross-process clock pair is
        needed inside the engine.  Absent/malformed context (legacy
        producers, old frames) degrades to no parent and no queue-wait
        span, never an error."""
        tc = rec.get("trace_ctx")
        if not isinstance(tc, dict):
            return
        tid = rec.get("trace_id")
        ctx = SpanContext.from_traceparent(tc.get("tp"))
        if ctx is not None:
            if tid is None:
                tid = rec["trace_id"] = ctx.trace_id
            if len(self._trace_meta) >= self._TRACE_META_CAP:
                for k in list(self._trace_meta)[
                        : self._TRACE_META_CAP // 2]:
                    self._trace_meta.pop(k, None)
            self._trace_meta[tid] = (ctx.span_id, ctx.sampled)
        ts = tc.get("ts")
        if isinstance(ts, (int, float)) and 0 < ts < float("inf"):
            wait_s = max((time.time_ns() - ts) / 1e9, 0.0)
            # clamp pathological skew (a producer clock far ahead/behind
            # would paint a day-long queue-wait bar across the timeline)
            wait_s = min(wait_s, 3600.0)
            self._qwait[rid] = wait_s
            if len(self._qwait) > self._TRACE_META_CAP:
                for k in list(self._qwait)[: self._TRACE_META_CAP // 2]:
                    self._qwait.pop(k, None)
            self._span("queue_wait", t_claim - wait_s, t_claim,
                       trace_id=tid, uri=rid)

    def _slo_observe(self, rid, e2e_s: float,
                     stages: Optional[Dict] = None,
                     tenant: Optional[str] = None) -> float:
        """Feed one completed record to the SLO tracker (no-op when no
        ``serving_slo`` block is configured) and the per-tenant burn
        view.  Queue-wait measured at claim is folded in both as a
        stage and into the judged latency, so "we missed the SLO
        queueing" is attributable.  Returns the folded e2e so the
        caller can charge ``serving_request_seconds`` batched per
        (tenant, flush) — the histogram hop is the only per-record
        metering cost left on the write worker, so it's amortized."""
        qwait = self._qwait.pop(rid, None)
        stages = dict(stages or {})
        if qwait is not None:
            stages["queue_wait"] = qwait
            e2e_s = float(e2e_s) + qwait
        # per-tenant burn views share the fleet objective unless the
        # metering block names per-tenant objectives (no objective
        # anywhere = no view; the meter no-ops)
        self.meter.slo_observe(tenant, e2e_s, stages)
        if self._slo is not None:
            self._slo.observe(e2e_s, stages)
        return float(e2e_s)

    # -- lease lifecycle (PR 5 horizontal replicas) --------------------------
    def _ack(self, rids: List[str]) -> None:
        """Release the claim on fully-handled records (result/quarantine/
        shed marker written).  A failed ack is NOT an error path: the
        records stay pending, some replica reclaims them after the lease,
        and duplicate suppression keeps the result set exact."""
        if not rids:
            return
        for rid in rids:
            self._inflight.pop(rid, None)
        try:
            self.queue.ack(list(rids))
        except Exception as e:  # noqa: BLE001 — backend down mid-ack
            logger.warning(
                "serving: ack failed for %d record(s) (%s: %s); they will "
                "be redelivered after the lease", len(rids),
                type(e).__name__, e)

    def _maybe_reclaim(self) -> List[Tuple[str, Dict]]:
        """Periodic reclaim sweep: re-claim records whose lease expired on
        a dead (or wedged) replica and feed the survivors into the normal
        pipeline.  Redelivered records that already HAVE a result — the
        previous owner wrote it but died before acking — are suppressed:
        acked here, counted, never re-predicted."""
        p = self.params
        if p.lease_s is None or p.lease_s <= 0:
            return []
        interval = p.reclaim_interval_s if p.reclaim_interval_s is not None \
            else max(p.lease_s / 2.0, 0.05)
        now = time.monotonic()
        if now - self._last_reclaim < interval:
            return []
        self._last_reclaim = now
        try:
            entries = self.queue.reclaim(
                p.lease_s, max_items=p.max_batch or p.batch_size)
        except Exception as e:  # noqa: BLE001 — backend down: next sweep
            logger.warning("serving: reclaim sweep failed (%s: %s)",
                           type(e).__name__, e)
            return []
        if not entries:
            return []
        # self-reclaim guard: records currently in OUR pipeline (a cold jit
        # compile, a long batch) can outlive the lease too — re-serving
        # them here would double-predict our own in-flight work.  The
        # queue-side reclaim already refreshed their lease under our
        # consumer name, which is exactly a lease extension; just don't
        # feed them back in.  Entries older than the stale bound are
        # assumed abandoned (a worker crashed mid-pipeline and the
        # supervisor restarted it) and become reclaimable again.
        stale_s = max(p.lease_s * 10.0, p.lease_s + 60.0)
        for rid, ts in list(self._inflight.items()):
            if now - ts > stale_s:
                self._inflight.pop(rid, None)
        own = [e for e in entries if e[0] in self._inflight]
        entries = [e for e in entries if e[0] not in self._inflight]
        if own:
            logger.debug(
                "serving: replica %s lease-extended %d of its own "
                "in-flight record(s) instead of self-reclaiming",
                self.replica_id, len(own))
        if not entries:
            return []
        self.reclaimed += len(entries)
        self._m_reclaimed.inc(len(entries))
        try:
            existing = self.queue.get_results(
                [rid for rid, _, _ in entries])
        except Exception:  # noqa: BLE001 — store down: skip suppression,
            existing = {}  # idempotent writes keep the result set exact
        out: List[Tuple[str, Dict]] = []
        t = time.monotonic()
        for rid, rec, deliveries in entries:
            tid = rec.get("trace_id") if isinstance(rec, dict) else None
            self._span("reclaim", t, t, trace_id=tid, uri=rid)
            prior = existing.get(rid)
            partial_n = 0
            if isinstance(prior, dict) and prior.get("partial"):
                # a PARTIAL streaming result (PR 12) is not a terminal
                # state: the previous owner died mid-generation, so the
                # record must be re-served, not suppressed — the fresh
                # terminal result overwrites the stale partial.  Its
                # token count survives as the wasted-work floor the
                # resume path (PR 20) tries to recover.
                try:
                    partial_n = int(prior.get("n") or 0)
                except (TypeError, ValueError):
                    partial_n = 0
                prior = None
            if prior is not None:
                self.duplicates += 1
                self._m_duplicates.inc()
                self._ack([rid])
                continue
            if isinstance(rec, dict):
                # claim lineage rides the record: a quarantine of this
                # record dead-letters WITH its delivery count, and the
                # result write stamps it for the client
                rec["deliveries"] = deliveries
            if 0 < p.max_deliveries < deliveries:
                # poison-pill parking (PR 10): a record that keeps getting
                # redelivered — e.g. it crashes every replica that claims
                # it, or its terminal write keeps failing — must not loop
                # through reclaim forever, burning a predict slot per lease.
                # Park it in the dead-letter queue (error result + entry,
                # claim released) where `manager replay` can resurrect it
                # after a fix.
                self._quarantine(
                    rid, "reclaim",
                    RuntimeError(
                        f"max-deliveries-exceeded: delivery "
                        f"{deliveries} > max_deliveries="
                        f"{p.max_deliveries}"),
                    record=rec if isinstance(rec, dict) else None,
                    trace_id=tid)
                continue
            self._redelivered[rid] = deliveries
            if self._batcher is not None and isinstance(rec, dict):
                # generation continuity (PR 20): attach the dead owner's
                # checkpointed resume state, or meter the restart cost
                resume = self._load_resume(rid, rec, partial_n)
                if resume is not None:
                    rec["_resume"] = resume
            out.append((rid, rec))
        if len(self._redelivered) > 4096:
            # fire-and-forget bound: entries are popped at write/quarantine/
            # shed; a pathological stream of never-completing redeliveries
            # must not grow the map without limit.  Records still in OUR
            # pipeline keep their entry — evicting them would strip the
            # "deliveries" lineage off results/dead-letters mid-flight.
            for rid in list(self._redelivered):
                if len(self._redelivered) <= 2048:
                    break
                if rid not in self._inflight:
                    self._redelivered.pop(rid, None)
        if out:
            logger.info(
                "serving: replica %s reclaimed %d orphaned record(s) "
                "(lease %.3gs, %d suppressed as duplicates)",
                self.replica_id, len(out), p.lease_s,
                len(entries) - len(out))
            self._event("reclaim", count=len(out),
                        suppressed=len(entries) - len(out))
        return out

    # -- generation continuity (PR 20) ---------------------------------------
    def _load_resume(self, rid: str, rec: Dict,
                     partial_n: int) -> Optional[Dict]:
        """Recover the dead owner's checkpointed decode state for one
        reclaimed generation record: follow the lease annotation to its
        snapshot spool, pick the deepest checkpoint of the matching
        epoch, and verify its integrity stamp.  Any failure falls back
        LOUDLY to restart-from-0 (`gen_resume_failed` event) and meters
        the streamed progress the restart throws away; a success emits
        `gen_resume` and meters only the partial tail past the last
        checkpoint."""
        gp = self._gen_params
        if gp is None or not gp.resume:
            # resume disabled: the restart re-computes every token the
            # dead owner already streamed — metered so the chaos bench's
            # restart arm measures its true waste
            if partial_n > 0:
                self._m_resume_wasted.inc(partial_n)
            return None
        try:
            ann = self.queue.annotation(rid)
        except Exception:  # noqa: BLE001 — backend hiccup: restart
            ann = None
        if not isinstance(ann, dict) or not ann.get("spool"):
            if partial_n > 0:
                self._m_resume_wasted.inc(partial_n)
                self._event("gen_resume_failed", rid=rid,
                            reason="no-annotation", wasted=partial_n)
            return None
        from analytics_zoo_tpu.serving import tracecollect
        spool = str(ann["spool"])
        epoch = int(ann.get("epoch") or 0)
        best = None
        try:
            paths = [path for path in (spool, spool + ".1")
                     if os.path.exists(path)]
            for snap in tracecollect.load_snapshots(paths):
                if snap.get("rid") != rid \
                        or int(snap.get("epoch") or 0) != epoch:
                    continue
                if best is None \
                        or int(snap.get("n") or 0) > int(best["n"] or 0):
                    best = snap
        except Exception:  # noqa: BLE001 — unreadable spool: restart
            best = None
        reason = None
        if best is None:
            reason = "no-snapshot"
        else:
            try:
                crc = int(best.get("crc"))
            except (TypeError, ValueError):
                crc = None
            if crc != tracecollect.snapshot_checksum(best):
                reason = "checksum-mismatch"
        tid = rec.get("trace_id")
        if reason is not None:
            self._m_resume_wasted.inc(partial_n)
            self._event("gen_resume_failed", rid=rid, trace_id=tid,
                        reason=reason, wasted=partial_n)
            return None
        n = int(best.get("n") or 0)
        wasted = max(0, partial_n - n)
        if wasted:
            self._m_resume_wasted.inc(wasted)
        self._event("gen_resume", rid=rid, trace_id=tid, epoch=epoch,
                    resumed_tokens=n, wasted=wasted,
                    from_replica=ann.get("replica"))
        return {"tokens": [int(t) for t in best.get("tokens") or []],
                "epoch": epoch + 1}

    # -- result write with backpressure (ClusterServing.scala:276-307) -------
    def _put_result(self, rid, value):
        """Retry with backoff (blocking: upstream reads stall), behind a
        circuit breaker — a dead result store fails fast instead of making
        every batch grind through the full retry schedule."""
        self._breaker.call(self._write_retry.call,
                           self.queue.put_result, rid, value)

    def _flush_results(self, pairs: List[Tuple[str, Dict]],
                       tmap: Optional[Dict] = None,
                       tenmap: Optional[Dict] = None) -> int:
        """Write one micro-batch of results in a single backend round-trip
        (`queue.put_results`), behind the same RetryPolicy + CircuitBreaker
        as single writes.  When the batch write fails (mid-way or wholesale),
        fall back to per-record writes: `put_result` is idempotent per key,
        so re-writing an already-committed pair cannot duplicate a result,
        and only the records that individually fail are quarantined.

        Records-served attribution (PR 19) is charged HERE — the one
        choke point both planes flush through — so exactly the records
        whose results were committed are billed, per tenant, on both the
        batched and the degraded per-record path."""
        if not pairs:
            return 0
        tenmap = tenmap or {}
        try:
            self._breaker.call(self._write_retry.call,
                               self.queue.put_results, pairs)
            # results durable: release the claims (at-least-once becomes
            # exactly-one-result here)
            self._ack([rid for rid, _ in pairs])
            # one charge per tenant per flush, not per record: the meter
            # hop is on the write worker's critical path
            by_tenant: Dict[Optional[str], int] = {}
            for rid, _ in pairs:
                ten = tenmap.get(rid)
                by_tenant[ten] = by_tenant.get(ten, 0) + 1
            for ten, n in by_tenant.items():
                self.meter.records(ten, n)
            return len(pairs)
        except Exception as e:  # noqa: BLE001 — batch path down: degrade
            if not isinstance(e, CircuitBreakerOpen):
                logger.warning(
                    "serving: batched result write failed (%s: %s); "
                    "falling back to per-record writes",
                    type(e).__name__, e)
            n = 0
            written: List[str] = []
            for rid, value in pairs:
                try:
                    self._put_result(rid, value)
                    written.append(rid)
                    n += 1
                    self.meter.records(tenmap.get(rid))
                except Exception as rec_exc:  # noqa: BLE001 — record down
                    # deliberate shed-don't-block tradeoff: when the result
                    # store is down past the retry budget the computed value
                    # is dead-lettered (client sees the error and can
                    # re-enqueue) instead of stalling the write worker
                    # behind an unbounded blocking retry
                    self._quarantine(rid, "put_result", rec_exc,
                                     trace_id=(tmap or {}).get(rid),
                                     tenant=tenmap.get(rid))
            self._ack(written)
            return n

    def _quarantine(self, rid, stage: str, exc: BaseException,
                    record: Optional[Dict] = None,
                    trace_id: Optional[str] = None,
                    tenant: Optional[str] = None):
        """Per-record fault isolation: the poisoned record gets an error
        RESULT (client unblocks and sees the failure) plus a dead-letter
        entry; the rest of its micro-batch proceeds untouched.  The span
        carries the error (and the record's trace_id when known), so the
        quarantine is diagnosable from the trace alone."""
        self.dead_lettered += 1
        self._m_quarantined.labels(stage=stage).inc()
        if tenant is None and record is not None:
            tenant = record.get("tenant")
        self.meter.sheds(tenant)       # attribution (PR 19): who lost it
        msg = f"{stage}: {type(exc).__name__}: {exc}"
        if trace_id is None and record is not None:
            trace_id = record.get("trace_id")
        now = time.monotonic()
        self._span(stage, now, now, trace_id=trace_id, uri=rid,
                         error=msg,
                         attrs=({"tenant": tenant} if tenant else None))
        logger.warning("serving: quarantining record %r (%s)", rid, msg)
        self._event("quarantine", rid=str(rid), stage=stage,
                    error=msg[:200], trace_id=trace_id, tenant=tenant)
        handled = False
        try:
            self._dead_breaker.call(self.queue.put_error, rid, msg,
                                    record=record, trace_id=trace_id)
            handled = True
        except CircuitBreakerOpen:
            # store is down: don't block per record on the dead backend
            logger.warning("serving: dead-letter write for %r skipped "
                           "(breaker open)", rid)
        except Exception:  # noqa: BLE001 — best-effort: queue may be down
            logger.exception("serving: dead-letter write for %r failed", rid)
        self._redelivered.pop(rid, None)
        if handled:
            # the quarantine is HANDLED (error result + dead-letter entry
            # are its terminal state, durably written): release the claim
            # so no replica churns it back through the pipeline forever
            self._ack([rid])
        else:
            # terminal write failed: the claim stays pending so the record
            # is REDELIVERED after the lease instead of silently lost (the
            # pre-lease contract shed it here).  It is no longer in OUR
            # pipeline, so drop the self-reclaim guard — any replica,
            # including this one, may retry it against a recovered store.
            self._inflight.pop(rid, None)

    # -- end-to-end deadlines (PR 2 availability) ----------------------------
    def _shed_expired(self, rid, rec: Optional[Dict],
                      deadline_ns: Optional[int] = None,
                      stage: str = "read",
                      trace_id: Optional[str] = None,
                      tenant: Optional[str] = None) -> bool:
        """True when the record's enqueue-stamped `deadline_ns` has passed:
        the client gets a `deadline-exceeded` error result and the record
        never occupies a predict slot.  The shed is recorded as a zero-width
        span at the gate's stage, error attached, so an expired record still
        shows up in its trace."""
        dl = deadline_ns if deadline_ns is not None \
            else (rec or {}).get("deadline_ns")
        if dl is None:
            return False
        try:
            expired = time.time_ns() > int(dl)
        except (TypeError, ValueError, OverflowError) as e:
            # this gate runs OUTSIDE the per-record quarantine: a junk
            # deadline from a raw-xadd producer would otherwise kill the
            # read worker, which restarts, redelivers the leased record,
            # and dies again — crash-loop, not fault isolation.  (The
            # gateway 400s these at the edge; this covers every other
            # producer.)  True = the record leaves the pipeline.
            self._quarantine(rid, stage, e, record=rec, trace_id=trace_id)
            return True
        if not expired:
            return False
        if rec is not None:
            if trace_id is None:
                trace_id = rec.get("trace_id")
            if tenant is None and isinstance(rec.get("tenant"), str):
                tenant = rec.get("tenant")
        self._shed_terminal(rid, stage=stage, trace_id=trace_id,
                            tenant=tenant)
        return True

    def _shed_terminal(self, rid, stage: str = "read",
                       trace_id: Optional[str] = None,
                       error: str = "deadline-exceeded: budget elapsed "
                                    "before predict",
                       extra: Optional[Dict] = None,
                       tenant: Optional[str] = None) -> None:
        """Terminal shed bookkeeping: error marker written (best-effort),
        claim released, counters/span recorded.  Shared by the deadline
        gates and the generation scheduler's step-boundary sheds;
        ``extra`` rides the marker (a mid-generation shed's partial
        tokens must survive the overwrite of the streamed partial)."""
        self.shed += 1
        self._m_shed.inc()
        self.meter.sheds(tenant)       # attribution (PR 19): who lost it
        now = time.monotonic()
        self._span(stage, now, now, trace_id=trace_id, uri=rid,
                         error=error,
                         attrs=({"tenant": tenant} if tenant else None))
        logger.info("serving: shedding expired record %r", rid)
        self._event("shed", rid=str(rid), stage=stage, trace_id=trace_id,
                    tenant=tenant)
        result = {"error": error}
        if extra:
            result.update(extra)
        if trace_id is not None:
            result["trace_id"] = trace_id
        try:
            self._put_result(rid, result)
        except Exception:  # noqa: BLE001 — store down: client's own
            pass           # deadline still unblocks it
        # shed = terminal (the budget is gone for every replica alike):
        # release the claim even when the marker write failed
        self._redelivered.pop(rid, None)
        self._ack([rid])

    def _claim_shed(self, rid, rec, to_shed) -> bool:
        """PR 17 claim gates, armored deployments only.  True when the
        record left the pipeline: either its priority class is being
        shed under the current pressure level, or the deadline early
        drop judged it unmeetable — remaining budget shorter than the
        estimated wait through the staged backlog at the smoothed
        per-batch service time (no estimate yet = never drop)."""
        from analytics_zoo_tpu.serving.admission import (
            deadline_unmeetable, normalize_priority)
        if not isinstance(rec, dict):
            return False
        trace_id = rec.get("trace_id")
        tenant = rec.get("tenant") \
            if isinstance(rec.get("tenant"), str) else None
        if to_shed:
            prio = normalize_priority(rec.get("priority"))
            if prio in to_shed:
                self._shed_terminal(
                    rid, stage="claim", trace_id=trace_id,
                    error=f"shed: {prio} class dropped under overload "
                          f"pressure", tenant=tenant)
                return True
        dl = rec.get("deadline_ns")
        if dl is not None and self._predict_ewma_s:
            try:
                remaining_s = (int(dl) - time.time_ns()) / 1e9
            except (TypeError, ValueError, OverflowError):
                return False     # junk deadline: _shed_expired's business
            backlog = 0
            for q in (getattr(self, "_staged", None),
                      getattr(self, "_writeq", None)):
                if q is not None:
                    backlog += q.qsize()
            if deadline_unmeetable(remaining_s, backlog,
                                   self._predict_ewma_s):
                self._shed_terminal(
                    rid, stage="claim", trace_id=trace_id,
                    error="deadline-unmeetable: estimated queue wait "
                          "exceeds the remaining budget", tenant=tenant)
                return True
        return False

    # -- adaptive micro-batching (PR 3 tentpole) -----------------------------
    def _read_coalesced(self):
        """Coalescing read: pull up to ``max_batch`` records, and once a
        PARTIAL batch has arrived keep reading for at most ``max_wait_ms``
        to fill a device-sized batch (the Structured-Streaming micro-batch
        coalescing analog).  An idle stream still returns empty within
        ``poll_timeout_s`` — the wait budget only starts when there is a
        first record to amortize it against."""
        p = self.params
        max_batch = p.max_batch or p.batch_size
        batch = self.queue.read_batch(max_batch, p.poll_timeout_s)
        if not batch or len(batch) >= max_batch or p.max_wait_ms <= 0:
            return batch
        deadline = time.monotonic() + p.max_wait_ms / 1000.0
        while len(batch) < max_batch:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            more = self.queue.read_batch(max_batch - len(batch),
                                         min(remaining, p.poll_timeout_s))
            if more:
                batch.extend(more)
        return batch

    def _stack_group(self, ids, items, deadlines, traces=None, t_read=None,
                     metas=None):
        """Stack one same-shape group into a staged
        (ids, tensors, scales, deadlines, traces) micro-batch."""
        t_ready = time.monotonic()
        if all(isinstance(it, QuantizedTensor) for it in items):
            # compact-dtype batch: ship the int8/uint8 bytes to the device,
            # dequantize there (per-row scales)
            tensors = np.stack([it.data for it in items])
            scales = np.asarray([it.scale for it in items], np.float32)
            return _Staged(ids, tensors, scales, deadlines, traces,
                           t_read, t_ready, metas)
        # mixed float/quantized batches dequantize the stragglers on host
        tensors = np.stack([
            it.data.astype(np.float32) * it.scale
            if isinstance(it, QuantizedTensor) else it for it in items])
        return _Staged(ids, tensors, None, deadlines, traces,
                       t_read, t_ready, metas)

    def _preprocess_pool(self):
        """Lazy thread pool for ``preprocess_workers > 1`` (base64 + cv2
        decode release the GIL, so a pool scales on multi-core hosts);
        ``None`` means inline preprocessing (the pre-PR-3 behaviour)."""
        if self.params.preprocess_workers <= 1:
            return None
        if self._pre_pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._pre_pool_size = self.params.preprocess_workers
            self._pre_pool = ThreadPoolExecutor(
                max_workers=self._pre_pool_size,
                thread_name_prefix="serving-pre")
        return self._pre_pool

    # -- live retune (PR 10 closed-loop autoscaling) -------------------------
    MAX_PREPROCESS_WORKERS = 32

    def retune(self, max_batch: Optional[int] = None,
               max_wait_ms: Optional[float] = None,
               preprocess_workers: Optional[int] = None,
               inflight_batches: Optional[int] = None) -> Dict:
        """Stage a live data-plane retune (the autoscaler's FAST actuator
        tier).  Values are validated/clamped HERE — ``max_batch`` to the
        pow-2 bucket ladder within [mesh batch axis, model max_batch],
        ``inflight_batches`` to the model's concurrency contract,
        ``preprocess_workers`` to [1, MAX_PREPROCESS_WORKERS] — and applied
        by the preprocess worker at its next batch boundary, so a mid-batch
        nudge can never tear the pipeline (pool swap and write-queue resize
        happen between micro-batches, on the threads that own them).
        Returns the clamped targets that will take effect.  Safe to call
        before ``start()`` (targets land in params directly at start)."""
        from analytics_zoo_tpu.inference.inference_model import _pow2_floor
        staged: Dict[str, float] = {}
        if max_batch is not None:
            mb = _pow2_floor(max(1, int(max_batch)))
            multiple = getattr(self.model, "_batch_multiple", 1) or 1
            cap = getattr(self.model, "max_batch", None)
            mb = max(mb, int(multiple))      # pow-2 >= multiple divides it
            if cap is not None:
                mb = min(mb, int(cap))
            staged["max_batch"] = mb
        if max_wait_ms is not None:
            staged["max_wait_ms"] = max(0.0, float(max_wait_ms))
        if preprocess_workers is not None:
            staged["preprocess_workers"] = min(
                max(1, int(preprocess_workers)), self.MAX_PREPROCESS_WORKERS)
        if inflight_batches is not None:
            inflight = max(1, int(inflight_batches))
            model_cap = getattr(self.model, "concurrent_num", None)
            if model_cap is not None:
                inflight = min(inflight, int(model_cap))
            staged["inflight_batches"] = inflight
        with self._knob_lock:
            self._pending_knobs.update(staged)
        return staged

    def knobs(self) -> Dict:
        """Current data-plane knob targets (pending retunes win over the
        applied params) — the autoscaler's view of where the fast tier is."""
        p = self.params
        doc = {"max_batch": p.max_batch or p.batch_size,
               "max_wait_ms": p.max_wait_ms,
               "preprocess_workers": p.preprocess_workers,
               "inflight_batches": p.inflight_batches,
               "max_batch_ceiling": int(getattr(self.model, "max_batch",
                                                1024) or 1024),
               "inflight_ceiling": int(getattr(self.model, "concurrent_num",
                                               None) or 64)}
        with self._knob_lock:
            doc.update(self._pending_knobs)
        return doc

    def _apply_pending_knobs(self) -> None:
        """Apply staged retunes.  Runs on the preprocess worker between
        micro-batches: `params.max_batch`/`max_wait_ms` are read per batch
        by `_read_coalesced`, the pool swap happens while no decode is in
        flight, and the write-queue resize mutates `maxsize` under the
        queue's own mutex (blocked putters poll on a 0.1 s timeout, so a
        grown queue is picked up promptly either way)."""
        with self._knob_lock:
            if not self._pending_knobs:
                return
            staged, self._pending_knobs = self._pending_knobs, {}
        p = self.params
        if "max_batch" in staged:
            p.max_batch = int(staged["max_batch"])
        if "max_wait_ms" in staged:
            p.max_wait_ms = float(staged["max_wait_ms"])
        if "preprocess_workers" in staged:
            p.preprocess_workers = int(staged["preprocess_workers"])
            if self._pre_pool is not None and \
                    self._pre_pool_size != p.preprocess_workers:
                # no decode in flight at the batch boundary: the old pool
                # has nothing queued, so the swap is clean
                self._pre_pool.shutdown(wait=False)
                self._pre_pool = None
        if "inflight_batches" in staged:
            p.inflight_batches = int(staged["inflight_batches"])
            q = getattr(self, "_writeq", None)
            if q is not None:
                with q.mutex:
                    q.maxsize = p.inflight_batches
                    q.not_full.notify_all()
        logger.info("serving: replica %s retuned %s", self.replica_id,
                    staged)
        self._event("retune", **{k: float(v) for k, v in staged.items()})

    def _read_and_preprocess(self):
        """Read one micro-batch and preprocess it record-by-record, returning
        a LIST of staged (ids, tensors, scales) groups — one per input shape.
        A malformed record (bad base64, undecodable image, byte/shape
        mismatch) quarantines alone; records with a different-but-valid shape
        form their own group (multi-shape clients are legitimate — the pow-2
        bucketing in InferenceModel compiles per signature anyway) instead of
        poisoning np.stack or being rejected for losing a batch vote.

        With ``preprocess_workers > 1`` the per-record decode fans out across
        the pool; results are gathered in submission order, so quarantine
        attribution and shape grouping are identical to the inline path.

        PR 5: the periodic reclaim sweep runs here, so records orphaned by
        a dead replica enter the pipeline ahead of fresh stream reads and
        go through the exact same shed/quarantine/trace machinery."""
        t0 = time.monotonic()
        self._hb_ts = t0      # replica heartbeat: the read loop is alive
        self._apply_pending_knobs()
        # brownout ladder tick (PR 17): feed the SLO burn rate in, so the
        # stage the gateway/scheduler consult tracks the live window
        self._brownout_tick()
        if self._faults.claim_active:
            # claim_stall fault (PR 17): a deterministic backlog-builder
            # for overload chaos — the read loop stalls BEFORE claiming
            stall = self._faults.take_claim_stall()
            if stall > 0.0:
                self._event("claim_stall", state=f"{stall:g}s")
                self._stop.wait(stall)
        if self._retiring.is_set():
            # decommissioning: claim NOTHING new (no reads, no reclaims) so
            # the pipeline flushes and the drain exit fires; the backlog
            # belongs to the surviving replicas
            return None
        batch = self._maybe_reclaim()
        batch += self._read_coalesced()
        t_read = time.monotonic()
        if not batch:
            return None       # stream empty (drain may exit on this)
        self._stages["read"].record(t_read - t0)
        bytes_by_tenant: Dict[Optional[str], int] = {}
        for rid, rec in batch:
            # claim registry for the self-reclaim guard: while a record is
            # in OUR pipeline the reclaim sweep must not mistake it for a
            # dead replica's orphan (cleared on ack)
            self._inflight[rid] = t_read
            # propagated span context (PR 13): parent/sampled for this
            # trace + the queue-wait span from the stamped ingest time
            self._note_trace_ctx(rid, rec, t_read)
            # every record that enters the pipeline gets a trace: producers
            # that bypass the client (raw xadd) are stamped at read instead
            rec.setdefault("trace_id", new_trace_id())
            # per-format wire-byte accounting (PR 7): frames carry their
            # exact length; legacy records are dominated by the b64 string.
            # Type-guarded — this loop runs outside the per-record
            # quarantine, and raw-xadd producers control these fields
            nbytes = rec.get("wire_bytes")
            if not (isinstance(nbytes, (int, float))
                    and 0 <= nbytes < float("inf")):
                # non-numeric, negative, inf, or NaN (NaN fails 0 <=):
                # inc()ing any of those poisons the monotonic counter for
                # the process lifetime
                raw = rec.get("b64") or rec.get("image") or ""
                nbytes = len(raw) \
                    if isinstance(raw, (str, bytes, bytearray)) else 0
            self._m_wire_bytes.labels(
                format=_wire_fmt_label(rec)).inc(nbytes)
            # usage attribution (PR 19): ingress bytes charged to the
            # tenant the gateway stamped (legacy records -> "unknown"),
            # accumulated locally and charged once per read batch
            ten = rec.get("tenant")
            ten = ten if isinstance(ten, str) else None
            bytes_by_tenant[ten] = bytes_by_tenant.get(ten, 0) + nbytes
            self._span("read", t0, t_read,
                             trace_id=rec["trace_id"], uri=rid)
        for ten, nb in bytes_by_tenant.items():
            self.meter.wire_bytes(ten, nb)
        # priority-ordered claim and shed (PR 17): interactive records
        # stage first; under pressure the lowest classes are shed before
        # they spend a predict slot, and a record that can no longer make
        # its deadline through the current backlog is dropped at claim
        # instead of timing out mid-pipeline.  Opt-in (self._armor) — an
        # unarmored deployment keeps the exact legacy claim path.
        from analytics_zoo_tpu.serving.admission import (
            PRIORITIES, normalize_priority, normalize_tenant, shed_classes)
        if self._armor:
            rank = {p: i for i, p in enumerate(PRIORITIES)}
            batch = sorted(
                batch, key=lambda kv: rank[normalize_priority(
                    kv[1].get("priority")
                    if isinstance(kv[1], dict) else None)])
            to_shed = shed_classes(self._pressure_level())
        else:
            to_shed = ()
        kept = []
        for rid, rec in batch:
            if self._shed_expired(rid, rec):
                continue
            if self._armor and self._claim_shed(rid, rec, to_shed):
                continue
            kept.append((rid, rec))

        def pre_one(rec):
            """Per-record timed decode, so one slow record is visible in
            its own preprocess span rather than smeared across the batch."""
            p0 = time.monotonic()
            out = self.preprocess(rec)
            return out, p0, time.monotonic()

        pool = self._preprocess_pool()
        items: List = []      # (rid, item, deadline_ns, trace_id)
        if pool is None:
            gathered = [(rid, rec, None) for rid, rec in kept]
        else:
            gathered = [(rid, rec, pool.submit(pre_one, rec))
                        for rid, rec in kept]
        for rid, rec, fut in gathered:
            try:
                item, p0, p1 = fut.result() if fut is not None \
                    else pre_one(rec)
                self._pre_fmt_hist.labels(
                    format=_wire_fmt_label(rec)).record(p1 - p0)
                self._span("preprocess", p0, p1,
                                 trace_id=rec.get("trace_id"), uri=rid)
                # per-record generation options (PR 12): `gen` rides the
                # record untyped — the scheduler validates/clamps values
                meta = rec.get("gen")
                meta = meta if isinstance(meta, dict) else None
                # identity hoist (PR 19): tenant must outlive the record
                # dict — batch formation, result docs, device-second
                # apportioning and generation-token charging all read it
                # off the meta.  None (not "unknown") for legacy records,
                # so the meter owns the fold in exactly one place.
                ten = rec.get("tenant")
                meta = dict(meta or {})
                meta["_tenant"] = normalize_tenant(ten) \
                    if isinstance(ten, str) and ten else None
                if self._armor:
                    # the brownout clamp (_submit_group) needs the class
                    # after the record dict is gone: ride it on the meta
                    meta["_priority"] = normalize_priority(
                        rec.get("priority"))
                if isinstance(rec.get("_resume"), dict):
                    # resume state stapled on by _maybe_reclaim (PR 20)
                    # must survive to _submit_group, like the identity
                    meta["_resume"] = rec["_resume"]
                items.append((rid, item, rec.get("deadline_ns"),
                              rec.get("trace_id"), meta))
            except Exception as e:  # noqa: BLE001 — malformed record
                self._quarantine(rid, "preprocess", e, record=rec)
        if kept:
            # one sample per micro-batch (like the other stage timers);
            # per-RECORD weighting is reserved for the e2e latency reservoir
            self._stages["preprocess"].record(time.monotonic() - t_read)
        groups: Dict[tuple, List] = {}
        for rid, item, dl, tid, meta in items:
            shape = np.shape(item.data if isinstance(item, QuantizedTensor)
                             else item)
            groups.setdefault(shape, []).append((rid, item, dl, tid, meta))
        if not groups:
            # records WERE read but all shed/quarantined: distinct from an
            # empty stream so a draining _pre_loop keeps reading the backlog
            return []
        return [self._stack_group([rid for rid, *_ in quints],
                                  [it for _, it, *_ in quints],
                                  [dl for _, _, dl, _, _ in quints],
                                  traces=[tid for *_, tid, _ in quints],
                                  t_read=t_read,
                                  metas=[m for *_, m in quints])
                for quints in groups.values()]

    def _predict_isolated(self, ids, tensors, scales, tmap=None):
        """Predict with graceful degradation: on failure, bisect the batch to
        isolate the poison input — sane rows still get answers, only the
        culprit is dead-lettered (log2(n) extra predict calls, worst case)."""
        try:
            return [(ids, self.model.do_predict(tensors, scales=scales))]
        except Exception as e:  # noqa: BLE001 — device/input failure
            return self._bisect_halves(ids, tensors, scales, e, tmap=tmap)

    def _bisect_halves(self, ids, tensors, scales, exc: BaseException,
                       tmap=None):
        """The bisect step shared by `_predict_isolated` and the write
        stage's readback-failure fallback: a single poisoned row is
        quarantined; a larger batch recurses on its halves.  ``tmap``
        (rid -> trace_id) keeps quarantine spans correlatable."""
        if len(ids) == 1:
            self._quarantine(ids[0], "predict", exc,
                             trace_id=(tmap or {}).get(ids[0]))
            return []
        mid = len(ids) // 2
        lo = self._predict_isolated(
            ids[:mid], tensors[:mid],
            None if scales is None else scales[:mid], tmap=tmap)
        hi = self._predict_isolated(
            ids[mid:], tensors[mid:],
            None if scales is None else scales[mid:], tmap=tmap)
        return lo + hi

    # -- async device pipeline (PR 3 tentpole) --------------------------------
    def _dispatch_batch(self, tensors, scales) -> _ResultHandle:
        """Dispatch one batch to the device WITHOUT blocking on the host
        readback (`InferenceModel.dispatch`): the write worker calls
        `.result()` downstream, so device compute overlaps both the next
        batch's preprocessing and the previous batch's result writes.

        A customized ``do_predict`` — instance-patched (chaos tests wrap it)
        OR overridden on a subclass (user shims) — must stay on the hot
        path unless the subclass customized ``dispatch`` alongside it, and
        bridge models may lack ``dispatch`` entirely: all of those fall
        back to a lazy synchronous call whose work (and failure) surfaces
        at `.result()` on the write stage."""
        model = self.model
        custom_predict = (
            "do_predict" in vars(model)
            or getattr(type(model), "do_predict", None)
            is not InferenceModel.do_predict)
        custom_dispatch = (
            "dispatch" in vars(model)
            or getattr(type(model), "dispatch", None)
            is not InferenceModel.dispatch)
        if not hasattr(model, "dispatch") or \
                (custom_predict and not custom_dispatch):
            return _LazyResult(
                lambda: model.do_predict(tensors, scales=scales))
        try:
            return model.dispatch(tensors, scales=scales)
        except Exception as e:  # noqa: BLE001 — trace/shape error at dispatch
            return _FailedDispatch(e)

    def _predict_stage(self, ids, tensors, scales=None, deadlines=None,
                       traces=None, t_read=None,
                       t_ready=None, metas=None) -> Optional[_InFlight]:
        """Deadline gate 2 + async dispatch.  Returns the in-flight handle
        for the write stage, or None when every record was shed."""
        # per-row tenant identity hoisted at preprocess rides the metas;
        # it must survive the gate-2 filter aligned with ids
        tenants = [m.get("_tenant") if isinstance(m, dict) else None
                   for m in (metas or [None] * len(ids))]
        # second deadline gate: a record can expire while staged behind a
        # slow predict — shed it here so the batch never wastes device time
        # on rows nobody is waiting for
        if deadlines is not None and any(d is not None for d in deadlines):
            keep = [i for i, (rid, dl) in enumerate(zip(ids, deadlines))
                    if not self._shed_expired(
                        rid, None, deadline_ns=dl, stage="stage_wait",
                        trace_id=traces[i] if traces else None,
                        tenant=tenants[i])]
            if not keep:
                return None
            if len(keep) < len(ids):
                ids = [ids[i] for i in keep]
                tensors = tensors[keep]
                if scales is not None:
                    scales = scales[keep]
                if traces is not None:
                    traces = [traces[i] for i in keep]
                tenants = [tenants[i] for i in keep]
        t0 = time.monotonic()
        if t_ready is not None:
            self._stages["stage_wait"].record(t0 - t_ready)
            for rid, tid in zip(ids, traces or [None] * len(ids)):
                self._span("stage_wait", t_ready, t0,
                                 trace_id=tid, uri=rid)
        handle = self._dispatch_batch(tensors, scales)
        return _InFlight(ids, tensors, scales, handle, traces, t_read, t0,
                         tenants)

    def _write_stage(self, inflight: _InFlight) -> int:
        """Block on the dispatched batch's host readback, postprocess per
        record, and flush the whole micro-batch of results in one batched
        write.  A readback failure falls straight into the bisect halves
        (the full batch was already tried once by the dispatch), preserving
        the log2(n) poison-isolation cost."""
        ids, tensors, scales = inflight.ids, inflight.tensors, inflight.scales
        tmap = dict(zip(ids, inflight.traces or []))
        tenmap = dict(zip(ids, inflight.tenants or []))
        try:
            chunks = [(ids, inflight.handle.result())]
        except Exception as e:  # noqa: BLE001 — device/input failure
            chunks = self._bisect_halves(ids, tensors, scales, e, tmap=tmap)
        t_done = time.monotonic()
        predict_wall = t_done - inflight.t_dispatch
        self._stages["predict"].record(predict_wall)
        self._note_predict_time(predict_wall)
        # device-second attribution (PR 19): the batch's measured dispatch
        # wall time is apportioned by row count over the rows that were
        # ACTUALLY dispatched — quarantined rows still burned the device,
        # so their tenant is still charged (conservation: Σ == wall)
        rows_by_tenant: Dict[Optional[str], int] = {}
        for rid in ids:
            ten = tenmap.get(rid)
            rows_by_tenant[ten] = rows_by_tenant.get(ten, 0) + 1
        self.meter.device_seconds(rows_by_tenant, predict_wall)
        pairs: List[Tuple[str, Dict]] = []
        for chunk_ids, probs in chunks:
            for rid, row in zip(chunk_ids, probs):
                ten = tenmap.get(rid)
                self._span("predict", inflight.t_dispatch, t_done,
                                 trace_id=tmap.get(rid), uri=rid,
                                 attrs=({"tenant": ten} if ten else None))
                try:
                    value = {"value": self.postprocess(np.asarray(row))}
                    if ten is not None:
                        # attribution rides the result doc so the gateway's
                        # result_poll span can tag the tenant without a
                        # side-channel lookup
                        value["tenant"] = ten
                    if self.model_version is not None:
                        # version identity (PR 16): clients can tell WHICH
                        # published version answered — mid-rollout, a
                        # mixed-version fleet answers with a mixed stream
                        value["model_version"] = self.model_version
                    if tmap.get(rid) is not None:
                        # PR 13: the trace rides the SUCCESS result too
                        # (error markers and generation finishes already
                        # carried it) — the gateway's result_poll span and
                        # the LB's lb_result span join the trace through
                        # it, closing the client-facing end of the
                        # reconstructed timeline
                        value["trace_id"] = tmap[rid]
                    deliveries = self._redelivered.pop(rid, None)
                    if deliveries:
                        # at-least-once made visible: the client can tell a
                        # failover-recovered result from a first delivery
                        value["deliveries"] = deliveries
                    pairs.append((rid, value))
                except Exception as e:  # noqa: BLE001 — per-record isolation
                    self._quarantine(rid, "postprocess", e,
                                     trace_id=tmap.get(rid), tenant=ten)
        n = self._flush_results(pairs, tmap=tmap, tenmap=tenmap)
        now = time.monotonic()
        if pairs:
            self._stages["write"].record(now - t_done)
            for rid, _ in pairs:
                self._span("write", t_done, now,
                                 trace_id=tmap.get(rid), uri=rid)
        if n and inflight.t_read is not None:
            self._e2e.record(now - inflight.t_read, n=n)
            # SLO attribution (PR 13): per-record stage decomposition —
            # queue_wait (folded in by _slo_observe), host pipeline
            # (preprocess + stage wait), device predict, result write
            t_read = inflight.t_read
            e2e_by_tenant: Dict[Optional[str], List[float]] = {}
            for rid, _ in pairs:
                ten = tenmap.get(rid)
                e2e = self._slo_observe(rid, now - t_read, {
                    "pipeline": max(inflight.t_dispatch - t_read, 0.0),
                    "predict": max(t_done - inflight.t_dispatch, 0.0),
                    "write": max(now - t_done, 0.0)},
                    tenant=ten)
                e2e_by_tenant.setdefault(ten, []).append(e2e)
            for ten, vals in e2e_by_tenant.items():
                self.meter.request_seconds_many(ten, vals)
        if n and self._awaits_first_result:
            self._capable("first_result", now)
        self.total_records += n
        dt = max(now - inflight.t_dispatch, 1e-9)
        if self._tb is not None:
            self._tb.add_scalar("Serving Throughput", n / dt,
                                self.total_records)
            self._tb.add_scalar("Total Records Number", self.total_records,
                                self.total_records)
        self._maybe_trim()
        return n

    def _maybe_trim(self):
        """Amortized memory guard: the XTRIM analog used to cost one backend
        round-trip per micro-batch; now it runs at most once per
        ``trim_interval_s`` (<= 0 restores the every-batch behaviour)."""
        interval = self.params.trim_interval_s
        if interval > 0:
            now = time.monotonic()
            if now - self._last_trim < interval:
                return
            self._last_trim = now
        self.queue.trim(self.params.stream_max_len)

    def _predict_and_write(self, ids, tensors, scales=None,
                           deadlines=None, traces=None, t_read=None,
                           t_ready=None, metas=None) -> int:
        """Synchronous predict+write for one staged group (serve_once and
        the write-stage fallbacks); the pipelined loop runs the same two
        stages on separate workers."""
        inflight = self._predict_stage(ids, tensors, scales=scales,
                                       deadlines=deadlines, traces=traces,
                                       t_read=t_read, t_ready=t_ready,
                                       metas=metas)
        if inflight is None:
            return 0
        return self._write_stage(inflight)

    # -- one micro-batch (synchronous path, used by tests/clients) -----------
    def serve_once(self) -> int:
        staged = self._read_and_preprocess()
        if self._batcher is not None:
            # generation mode: run the scheduler to quiescence — reads one
            # micro-batch, then steps until every admitted request reached
            # a terminal state (tests and embedded callers)
            clock = self._batcher.clock
            clock.to("intake")
            for group in staged or ():
                self._submit_group(group)
            before = self.total_records
            while not self._batcher.idle and not self._stop.is_set():
                self._gen_tick()
            clock.to("idle")
            return self.total_records - before
        if not staged:
            return 0
        return sum(self._predict_and_write(*group) for group in staged)

    # -- lifecycle (cluster-serving-start/stop scripts parity) ----------------
    def start(self):
        """Pipelined loop, three supervised stages (PR 3 data plane):

        - ``serving-preprocess`` reads coalesced micro-batches and fans the
          per-record decode across the preprocess pool;
        - ``serving-predict`` gates deadlines and DISPATCHES batches to the
          device without blocking on readback (up to ``inflight_batches``
          in flight);
        - ``serving-write`` blocks on each readback, postprocesses, and
          flushes results in one batched write per micro-batch.

        Host preprocess, device compute, and result writing all overlap; the
        two bounded hand-off buffers give natural backpressure when a
        downstream stage falls behind.

        All workers run SUPERVISED (PR 1): an escaping exception no longer
        kills the loop silently — it is logged, the worker restarts with
        backoff up to `params.max_worker_restarts`, and `health()` reports
        state/restarts/last error."""
        import queue as _q
        p = self.params
        self._stop.clear()
        self._draining.clear()
        self._retiring.clear()
        self._t_start = time.monotonic()
        try:
            # a prior drained shutdown closed admission; serving again means
            # taking traffic again
            self.queue.open_admission()
        except Exception:  # noqa: BLE001 — backend down: workers will report
            pass
        # bind the probe server FIRST: a port conflict must fail start()
        # before any worker thread begins consuming the queue
        if p.http_port is not None and self._http is None:
            from analytics_zoo_tpu.serving.http import HealthServer
            self._http = HealthServer(self, host=p.http_host,
                                      port=p.http_port).start()
        # zero cold start (PR 11): persistent compile cache + AOT warm-up.
        # The warm-up runs on its own thread so the pipeline serves (and
        # compiles lazily) meanwhile; /readyz reports `warming` with
        # per-program progress until the set is compiled, so the front
        # door routes around a still-cold replica instead of eating its
        # compile latency.
        from analytics_zoo_tpu.inference import aot
        aot.enable_persistent_cache(p.compile_cache_dir)
        if p.warmup and isinstance(self.model, InferenceModel):
            self._start_warmup()
        self._staged = _q.Queue(maxsize=p.pipeline_depth)
        # dispatch() takes no semaphore, so the engine is what bounds
        # device-resident batches: the handle queue holds `inflight`, plus
        # one mid-readback in the writer and one held by the predict worker
        # awaiting a slot — `inflight + 2` total.  Clamp the queue to the
        # model's supported_concurrent_num so that total never exceeds the
        # model's contract + 2 (the README sizing guidance)
        inflight = max(1, p.inflight_batches)
        model_cap = getattr(self.model, "concurrent_num", None)
        if model_cap is not None and inflight > model_cap:
            logger.warning(
                "serving: inflight_batches=%d exceeds the model's "
                "supported_concurrent_num=%d; clamping the handle queue "
                "(up to %d batches stay transiently resident)",
                inflight, model_cap, model_cap + 2)
            inflight = max(1, model_cap)
        self._writeq = _q.Queue(maxsize=inflight)
        self._last_trim = time.monotonic()
        self._pre_sup = SupervisedThread(
            self._pre_loop, name="serving-preprocess",
            max_restarts=p.max_worker_restarts,
            backoff_s=p.worker_backoff_s, stop_event=self._stop)
        if self._batcher is not None:
            # continuous batching (PR 12): ONE generate worker owns both
            # decode stepping and result writing — results must flush AT
            # step boundaries (a finished request unblocks its client
            # immediately), so splitting the stages would only add a
            # hand-off queue between two things that must stay in lockstep
            self._predict_sup = SupervisedThread(
                self._generate_loop, name="serving-generate",
                max_restarts=p.max_worker_restarts,
                backoff_s=p.worker_backoff_s, stop_event=self._stop)
            self._write_sup = None
        else:
            self._predict_sup = SupervisedThread(
                self._predict_loop, name="serving-predict",
                max_restarts=p.max_worker_restarts,
                backoff_s=p.worker_backoff_s, stop_event=self._stop)
            self._write_sup = SupervisedThread(
                self._write_loop, name="serving-write",
                max_restarts=p.max_worker_restarts,
                backoff_s=p.worker_backoff_s, stop_event=self._stop)
        self._pre_sup.start()
        self._predict_sup.start()
        if self._write_sup is not None:
            self._write_sup.start()
        self._event("start", mode=("generation" if self._batcher is not None
                                   else "predict"),
                    max_batch=p.max_batch or p.batch_size,
                    quantized_bits=self._quantized_bits() or None)
        # compat aliases: the raw threads, for callers that poked at them
        self._pre_thread = self._pre_sup._thread
        self._thread = self._predict_sup._thread
        return self

    # -- AOT warm-up (PR 11 zero cold start) ---------------------------------
    def _start_warmup(self) -> None:
        """Derive the warm-up manifest and compile it on a daemon thread.
        An underivable manifest (no declared input shape and no spec) is a
        warning, not a failed start — the deployment just stays on the
        lazy-compile path it had before PR 11."""
        from analytics_zoo_tpu.inference import aot
        p = self.params
        try:
            if self._batcher is not None:
                # continuous batching (PR 12): the warm-up set is the
                # scheduler's (prefill-bucket x decode-step) program set,
                # so a warm replica serves its first TOKEN with zero
                # compiles
                manifest = self._batcher.warmup_manifest()
            else:
                manifest = aot.resolve_manifest(self.model, p.warmup)
        except Exception as e:  # noqa: BLE001 — stay on the lazy path
            logger.warning(
                "serving: warm-up disabled — manifest underivable (%s: "
                "%s); pass warmup={'shape': [...]} in params",
                type(e).__name__, e)
            self._warm_state.update(state="off", error=str(e))
            return
        # `pending` BEFORE the thread runs: a /readyz scraped between
        # start() and the first compile must already say warming
        self._warm_state.update(state="pending", total=len(manifest),
                                compiled=0, failed=0, seconds=None)
        self._event("warmup", state="pending", total=len(manifest))
        self._warm_thread = threading.Thread(
            target=self._warmup_loop, args=(manifest,),
            name="serving-warmup", daemon=True)
        self._warm_thread.start()

    def _capable(self, mark: str, t: float) -> None:
        """``ready`` (the warm-up pass is done) or ``first_result`` (a
        result was written) at ``t``.  Construction to serving-capable,
        the number the autoscaler's actuation lag is made of, stops at
        whichever comes first: the first result (a backlog was waiting —
        the bench's spawn-to-first-result) or warm-up completion (an idle
        boot must not count time spent waiting for traffic)."""
        if mark == "first_result":
            self._awaits_first_result = False
        self.startup.stamp(mark, t)
        if self._cold_start_s is None:
            self._cold_start_s = t - self.startup.get("engine")
            self._g_cold.set(self._cold_start_s)

    def _warmup_loop(self, manifest) -> None:
        from analytics_zoo_tpu.inference import aot
        t_begin = self.startup.stamp("warm_begin")
        self._g_warm.labels(phase="init").set(
            t_begin - self.startup.get("engine"))
        self._warm_state["state"] = "warming"
        self._event("warmup", state="warming",
                    total=self._warm_state.get("total"))
        # fault point (PR 16): an armed warmup_crash kills the PROCESS
        # here — a real crash mid-warm-up, exercising the supervisor's
        # respawn-at-assigned-version path, not the exception handler below
        self._faults.check_warmup()

        def progress(done, total, entry):
            self._warm_state["compiled"] = done

        try:
            if self._batcher is not None:
                stats = self._batcher.warm(manifest, progress=progress,
                                           stop=self._stop.is_set)
            else:
                stats = aot.warm_up(self.model, manifest, progress=progress,
                                    stop=self._stop.is_set)
        except Exception as e:  # noqa: BLE001 — a warm-up crash must not
            # block readiness forever; the lazy path still serves
            logger.exception("serving: warm-up pass failed")
            self._warm_state.update(state="failed", error=str(e))
            self._event("warmup", state="failed", error=str(e)[:200])
            return
        if stats.get("stopped"):
            self._warm_state.update(state="cancelled")
            return
        # serving-capable without having seen traffic yet: the replica is
        # warm — the clock stops here, not at the first record (and before
        # the state says so: who reads `ready` finds the mark)
        self._capable("ready", time.monotonic())
        self._warm_state.update(
            state="ready" if not stats["failed"] else "degraded",
            failed=stats["failed"], errors=stats["errors"],
            seconds=stats["seconds"],
            compile_stats=stats["compile_stats"])
        self._event("warmup",
                    state="ready" if not stats["failed"] else "degraded",
                    programs=stats["programs"], failed=stats["failed"],
                    seconds=stats["seconds"])
        self._g_warm.labels(phase="compile").set(float(stats["seconds"]))
        if self._batcher is not None:
            totals = aot.startup_totals(list(self._batcher.program_records))
            for phase in ("lower", "backend", "retrieval"):
                self._g_warm.labels(phase=phase).set(
                    totals["startup_s." + phase])
        logger.info(
            "serving: replica %s warm — %d/%d program(s) in %.2fs (%s "
            "backend compile(s), %s persistent-cache hit(s))",
            self.replica_id, stats["programs"] - stats["failed"],
            stats["programs"], stats["seconds"],
            stats["compile_stats"]["cache_misses"],
            stats["compile_stats"]["cache_hits"])

    def warmup_state(self) -> Dict:
        """Warm-up progress document (health doc / readyz / manager
        status surface)."""
        programs = [] if self._batcher is None \
            else [dict(r) for r in list(self._batcher.program_records)]
        return dict(self._warm_state, programs=programs)

    def _pre_loop(self):
        sup = self._pre_sup
        while not self._stop.is_set():
            if sup is not None:
                sup.heartbeat()
            staged = self._read_and_preprocess()
            if not staged:
                # None = stream empty; [] = batch read but fully shed/
                # quarantined — only the former may end a drain, and only
                # when the backend is actually reachable: an outage ALSO
                # reads as an empty batch, but its backlog is still out
                # there, so keep polling until it heals or the drain budget
                # hard-stops us
                if staged is None and self._draining.is_set():
                    try:
                        if self.queue.read_path_healthy():
                            return     # drain: stream empty, clean exit
                    except Exception:  # noqa: BLE001 — state unknown
                        pass
                time.sleep(0.005)
                continue
            for group in staged:
                while not self._stop.is_set():
                    try:
                        self._staged.put(group, timeout=0.1)
                        break
                    except _FULL:
                        # buffer full: backpressure.  Still alive — stamp
                        # the heartbeat so a saturated replica doesn't read
                        # as dead to the autoscaler's stale-replica check
                        self._hb_ts = time.monotonic()
                        continue

    def _predict_loop(self):
        import queue as _q
        sup = self._predict_sup
        while not self._stop.is_set():
            if sup is not None:
                sup.heartbeat()
            try:
                group = self._staged.get(timeout=0.1)
            except _q.Empty:
                # drain exit: ONLY once the pre worker is dead AND the buffer
                # is (still) empty — is_alive first, so a group staged just
                # before the pre worker exited is seen by the empty() check
                if self._draining.is_set() and self._pre_sup is not None \
                        and not self._pre_sup.is_alive() \
                        and self._staged.empty():
                    return             # drain: upstream done + buffer empty
                continue
            inflight = self._predict_stage(*group)
            if inflight is None:
                continue               # whole group shed at gate 2
            while not self._stop.is_set():
                try:
                    self._writeq.put(inflight, timeout=0.1)
                    break
                except _FULL:
                    continue           # device pipeline full: backpressure

    def _write_loop(self):
        import queue as _q
        sup = self._write_sup
        while not self._stop.is_set():
            if sup is not None:
                sup.heartbeat()
            try:
                inflight = self._writeq.get(timeout=0.1)
            except _q.Empty:
                # drain exit mirrors _predict_loop: predict worker dead AND
                # nothing left in flight
                if self._draining.is_set() and self._predict_sup is not None \
                        and not self._predict_sup.is_alive() \
                        and self._writeq.empty():
                    return             # drain: upstream done + buffer empty
                continue
            self._write_stage(inflight)

    # -- continuous batching (PR 12 tentpole) ---------------------------------
    def _submit_group(self, group: _Staged) -> None:
        """Unpack one staged micro-batch into per-record generation
        requests and feed them to the scheduler.  The waiting room is
        bounded: when full, the generate loop keeps stepping (finishing
        requests frees it) instead of dropping records."""
        from analytics_zoo_tpu.serving.generate import GenRequest
        tensors = group.tensors
        if group.scales is not None:
            # int8-wire prompts: dequantize on host — token ids survive
            # the round-trip exactly when the producer quantized ids
            tensors = tensors.astype(np.float32) \
                * np.asarray(group.scales)[:, None]
        metas = group.metas or [None] * len(group.ids)
        traces = group.traces or [None] * len(group.ids)
        deadlines = group.deadlines or [None] * len(group.ids)
        for i, rid in enumerate(group.ids):
            meta = metas[i] if isinstance(metas[i], dict) else {}
            mt = meta.get("max_tokens")
            try:
                mt = None if mt is None else int(mt)
            except (TypeError, ValueError):
                mt = None
            if self._brownout is not None:
                # brownout stage 2 (PR 17): clamp generation length for
                # non-interactive traffic — lower-only, never a raise
                clamp = self._brownout.clamp_max_tokens(
                    meta.get("_priority", "batch"))
                if clamp is not None:
                    mt = clamp if mt is None else min(mt, clamp)
            resume = meta.get("_resume")
            rtoks, epoch = None, 0
            if isinstance(resume, dict):
                rtoks = resume.get("tokens") or None
                try:
                    epoch = int(resume.get("epoch") or 0)
                except (TypeError, ValueError):
                    epoch = 0
            req = GenRequest(rid, np.asarray(tensors[i]),
                             deadline_ns=deadlines[i],
                             trace_id=traces[i], t_read=group.t_read,
                             max_tokens=mt, tenant=meta.get("_tenant"),
                             resume_tokens=rtoks, epoch=epoch)
            if self.snapshot_path is not None \
                    and (self._gen_params.checkpoint_interval or 0) > 0:
                # ownership + resume state travel together (PR 20): the
                # lease annotation points the NEXT owner at this
                # replica's snapshot spool under this epoch
                try:
                    self.queue.annotate(rid, {
                        "spool": self.snapshot_path,
                        "epoch": epoch,
                        "replica": self.replica_id})
                except Exception:  # noqa: BLE001 — best-effort: a lost
                    pass           # annotation degrades to restart-from-0
            while not self._batcher.submit(req):
                if self._stop.is_set():
                    return
                # full boundary bookkeeping (not a bare step): tokens
                # emitted while the waiting room blocks are still charged
                # to their tenants at the step boundary
                self._gen_tick()
                self._batcher.clock.to("intake")

    def _gen_tick(self) -> None:
        """One decode-step boundary + its bookkeeping (stage timer,
        decode-step counter, tokens/sec window, per-boundary decode
        spans)."""
        b = self._batcher
        t0 = time.monotonic()
        events = b.step()
        b.clock.to("bookkeep")
        now = time.monotonic()
        if b.active or events:
            # step()'s whole wall (admission, prefill, decode and fold in
            # one number; the phase clock splits it): the autoscaler's
            # predict_p99_ms reads it for the generate plane too
            self._stages["predict"].record(now - t0)
        # per-boundary decode spans (PR 13): one span per request per
        # boundary, carrying tokens-emitted — the spans TTFT decomposes
        # into (prefill -> first boundary -> ...).  This is the per-token
        # span volume trace_sample exists to govern; the span wrapper
        # applies the same head-sampling verdict fleet-wide.
        rows_by_tenant: Dict[Optional[str], int] = {}
        for rid, tid, emitted, ten in b.last_boundary:
            attrs = {"tokens": emitted}
            if ten is not None:
                attrs["tenant"] = ten
            self._span("decode", t0, now, trace_id=tid, uri=rid,
                       attrs=attrs)
            # generation tokens are charged per tenant at each step
            # boundary (PR 19) — not at finish, so a long generation
            # bills as it burns and a mid-flight shed stays charged
            self.meter.tokens(ten, emitted)
            rows_by_tenant[ten] = rows_by_tenant.get(ten, 0) + 1
        # step wall time apportioned by slot occupancy at this boundary —
        # the generation plane's device-seconds attribution
        self.meter.device_seconds(rows_by_tenant, now - t0)
        steps = b.decode_steps
        if steps > self._last_steps:
            self._m_decode_steps.inc(steps - self._last_steps)
            self._last_steps = steps
        self._update_tps(now)
        # generation continuity (PR 20): spool this boundary's
        # checkpoints BEFORE the crash fault below, so an injected
        # mid-decode kill dies with its resume state already durable —
        # the same ordering a real preemption depends on
        self._maybe_checkpoint()
        if b.resumed > self._last_resumed:
            self._m_resumed.inc(b.resumed - self._last_resumed)
            self._last_resumed = b.resumed
        if self._faults.decode_crash_active \
                and self._faults.take_decode_crash(b.generated_tokens):
            logger.error(
                "faults: injected decode_crash_after_n_tokens (%d "
                "generated) — exiting", b.generated_tokens)
            os._exit(3)
        kinds = [ev.kind for ev in events]
        if any(k in ("finish", "shed", "quarantine") for k in kinds) or \
                b.last_admitted:
            # scheduler-boundary event (PR 15): recorded only when the
            # slot population changed — per-quantum decode churn would
            # otherwise dominate the ring without adding forensic signal
            self._event("gen_boundary", active=b.active,
                        waiting=b.waiting,
                        admitted=b.last_admitted,
                        finished=kinds.count("finish"),
                        shed=kinds.count("shed"),
                        quarantined=kinds.count("quarantine"))
        self._handle_gen_events(events)

    def _maybe_checkpoint(self) -> None:
        """Drain the scheduler's queued resume snapshots into the
        per-replica gensnap spool (the tracecollect rotation/clock
        contract), stamping each with its integrity checksum — which the
        armed ``snapshot_corrupt`` fault deliberately breaks, so the
        resume path's verification is provable.  Engines without a wired
        ``snapshot_path`` (the manager sets it next to the pidfile)
        discard the drained batch: checkpointing is durable-or-off,
        never silently buffered."""
        b = self._batcher
        if not b.pending_checkpoints:
            return
        snaps = b.drain_checkpoints()
        if self.snapshot_path is None:
            return
        from analytics_zoo_tpu.serving import tracecollect
        corrupt = self._faults.snapshot_corrupt_active
        for rec in snaps:
            crc = tracecollect.snapshot_checksum(rec)
            if corrupt:
                crc ^= 0x5A5A5A5A
            rec["crc"] = crc
        try:
            tracecollect.append_snapshots(self.snapshot_path, snaps,
                                          source=self.replica_id)
            size = 0
            for path in (self.snapshot_path, self.snapshot_path + ".1"):
                try:
                    size += os.path.getsize(path)
                except OSError:
                    pass
            b.snapshot_bytes = size
            self._event("gen_checkpoint", count=len(snaps),
                        tokens=sum(int(r.get("n") or 0) for r in snaps),
                        spool_bytes=size)
        except Exception as e:  # noqa: BLE001 — a full/readonly disk
            # must not take decode down; resume degrades to older
            # snapshots (or restart-from-0), both loud on the other side
            logger.warning("serving: checkpoint spool write failed "
                           "(%s: %s)", type(e).__name__, e)

    def _update_tps(self, now: float) -> None:
        """Roll the tokens/sec rate window.  Called from every generate
        loop iteration — including idle ones, so the gauge decays to 0
        when traffic stops instead of freezing at the last burst's
        rate."""
        wt0, wtok = self._tps_window
        if now - wt0 >= 1.0:
            self._g_tps.set((self._batcher.generated_tokens - wtok)
                            / max(now - wt0, 1e-9))
            self._tps_window = (now, self._batcher.generated_tokens)

    def _handle_gen_events(self, events) -> None:
        """Turn scheduler events into the existing record contracts:
        finish -> batched result write + ack (+ e2e/cold-start stamps),
        partial -> best-effort streaming overwrite, shed -> terminal
        deadline marker, quarantine -> dead-letter, first_token -> TTFT."""
        self._batcher.clock.to("flush")
        pairs: List[Tuple[str, Dict]] = []
        finals = []
        for ev in events:
            if ev.t_out is not None:
                # first output event of the request (partial or finish):
                # the wait for stream_interval tokens after the first one
                self._span("first_out", ev.t_first, ev.t_out,
                           trace_id=ev.trace_id, uri=ev.rid)
            if ev.kind == "first_token":
                self._m_ttft.record(ev.ttft_s)
                # the scheduler's own stamps (PR 25): waiting room, then
                # batch assembly + prefill program — the two hops
                # between queue_wait and the first decode boundary in
                # the TTFT decomposition; they sum to ttft_s
                self._span("sched_wait", ev.t_first - ev.ttft_s,
                           ev.t_admit, trace_id=ev.trace_id, uri=ev.rid)
                self._span("prefill", ev.t_admit, ev.t_first,
                           trace_id=ev.trace_id, uri=ev.rid)
            elif ev.kind == "partial":
                if self._brownout is not None \
                        and self._brownout.suppress_partials:
                    # brownout stage 1 (PR 17): partials are progress
                    # cosmetics — under SLO burn the write bandwidth
                    # goes to finals; the terminal result still flows
                    continue
                value = {"partial": True, "tokens": ev.tokens,
                         "n": len(ev.tokens)}
                if ev.trace_id is not None:
                    value["trace_id"] = ev.trace_id
                try:
                    # streaming is best-effort: a failed partial write
                    # must not retry-storm or quarantine a LIVE request —
                    # the next interval (or the terminal write)
                    # overwrites.  put_partial (PR 20) refuses to shadow
                    # a terminal: after a resume, the DEAD owner's last
                    # partial may still be in flight from its dying
                    # process, and one lineage must converge on the
                    # resumed terminal.
                    self.queue.put_partial(ev.rid, value)
                except Exception:  # noqa: BLE001
                    pass
            elif ev.kind == "finish":
                value = {"value": {"tokens": ev.tokens,
                                   "length": len(ev.tokens),
                                   "finish_reason": ev.finish_reason}}
                if ev.trace_id is not None:
                    value["trace_id"] = ev.trace_id
                if ev.tenant is not None:
                    value["tenant"] = ev.tenant
                deliveries = self._redelivered.pop(ev.rid, None)
                if deliveries:
                    value["deliveries"] = deliveries
                pairs.append((ev.rid, value))
                finals.append(ev)
                # tokens were already charged per tenant at each step
                # boundary (_gen_tick); nothing to double-count here
            elif ev.kind == "shed":
                # an ACTIVE request's shed event carries its progress:
                # say so ("before predict" would point triage at queueing
                # when the cost was decode time) and keep the tokens ON
                # the marker — the marker overwrites any streamed
                # partial, and default clients never return partials
                if ev.tokens is not None:
                    err = ("deadline-exceeded: budget elapsed "
                           f"mid-generation after {len(ev.tokens)} "
                           "token(s)")
                    extra = {"tokens": ev.tokens, "n": len(ev.tokens)}
                else:
                    err = "deadline-exceeded: budget elapsed before decode"
                    extra = None
                self._shed_terminal(ev.rid, stage="generate",
                                    trace_id=ev.trace_id, error=err,
                                    extra=extra, tenant=ev.tenant)
            elif ev.kind == "quarantine":
                self._quarantine(ev.rid, "generate",
                                 RuntimeError(ev.error or "generation "
                                                          "failed"),
                                 trace_id=ev.trace_id, tenant=ev.tenant)
            elif ev.kind == "resume_failed":
                # scheduler-level downgrade (PR 20): the resume prefix
                # could not be replayed (bare-state model, malformed
                # prefix, capacity) — the request restarts from 0; its
                # prefix is recomputed work, metered as wasted
                wasted = len(ev.tokens or ())
                if wasted:
                    self._m_resume_wasted.inc(wasted)
                self._event("gen_resume_failed", rid=ev.rid,
                            trace_id=ev.trace_id, reason=ev.error,
                            wasted=wasted)
        if not pairs:
            return
        tmap = {ev.rid: ev.trace_id for ev in finals}
        tenmap = {ev.rid: ev.tenant for ev in finals}
        n = self._flush_results(pairs, tmap=tmap, tenmap=tenmap)
        now = time.monotonic()
        for ev in finals:
            self._span("write", now, now, trace_id=ev.trace_id, uri=ev.rid)
            if ev.t_read is not None:
                self._e2e.record(now - ev.t_read)
                # SLO attribution: decode wall vs everything else; the
                # queue-wait measured at claim folds in via _slo_observe
                stages = {}
                if ev.wall_s is not None:
                    stages["decode"] = max(float(ev.wall_s), 0.0)
                e2e = self._slo_observe(ev.rid, now - ev.t_read, stages,
                                        tenant=ev.tenant)
                self.meter.request_seconds(ev.tenant, e2e)
        if n and self._awaits_first_result:
            self._capable("first_result", now)
        self.total_records += n
        self._maybe_trim()

    def _generate_loop(self):
        """The serving-generate worker: slot-map continuous batching
        between preprocess and the result store.  Staged micro-batches are
        unpacked into per-record requests; the scheduler admits them into
        free decode slots at step boundaries, finished requests flush
        immediately, and the loop never busy-spins an idle device (empty
        scheduler -> blocking read on the staged queue)."""
        import queue as _q
        sup = self._predict_sup
        b = self._batcher
        while not self._stop.is_set():
            if sup is not None:
                sup.heartbeat()
            # idle scheduler: block briefly for new work; busy: only sweep
            # what is already staged, then take the next decode step
            try:
                if b.idle:
                    b.clock.to("idle")
                    group = self._staged.get(timeout=0.1)
                    b.clock.to("intake")      # a timeout stays idle
                else:
                    b.clock.to("intake")
                    group = self._staged.get_nowait()
            except _q.Empty:
                group = None
            if group is not None:
                self._submit_group(group)
                while True:
                    try:
                        self._submit_group(self._staged.get_nowait())
                    except _q.Empty:
                        break
            if b.idle:
                self._update_tps(time.monotonic())
                if self._draining.is_set() and self._pre_sup is not None \
                        and not self._pre_sup.is_alive() \
                        and self._staged.empty():
                    return             # drain: upstream done + slots empty
                continue
            self._gen_tick()

    def stage_metrics(self) -> Dict:
        """Per-stage timing document (PR 3): read / preprocess / stage_wait /
        predict (dispatch -> host readback done) / write counters with
        p50/p99 over recent samples, plus ``e2e`` — per-record latency from
        read_batch return to result written."""
        doc = {name: st.snapshot() for name, st in self._stages.items()}
        doc["e2e"] = self._e2e.snapshot()
        return doc

    def _quantized_bits(self) -> int:
        """0 float, 8 W8A8, 4 W4A16 — what the loaded model serves with.
        Fixed after construction, so computed once and cached: health()
        backs the /healthz poll loops and must not re-flatten a large
        params tree per scrape."""
        if self._qbits is None:
            try:
                from analytics_zoo_tpu.inference.quantize import (
                    quantized_bits)
                self._qbits = quantized_bits(
                    getattr(self.model, "_params", None) or {})
            except Exception:  # noqa: BLE001 — bridge models, exotic params
                self._qbits = 0
        return self._qbits

    def _resources_doc(self) -> Dict:
        """The health-doc ``resources`` block (never raises — a probe
        must answer even when a component read fails mid-reload)."""
        try:
            return self._ledger.doc()
        except Exception as e:  # noqa: BLE001
            return {"error": f"{type(e).__name__}: {e}"}

    @staticmethod
    def _process_doc() -> Dict:
        from analytics_zoo_tpu.common.observability import process_stats
        try:
            return process_stats()
        except Exception:  # noqa: BLE001
            return {}

    def drain_usage(self) -> List[Dict]:
        """Per-interval usage deltas since the last drain (PR 19) — the
        manager's 1 s loop appends them to the per-replica usage journal
        next to the span/event spools."""
        return self.meter.drain()

    def health(self) -> Dict:
        """Serving health surface (manager `status` / ops, `/healthz`):
        worker states, restart counts, breaker state, record/dead-letter/
        shed counters, per-stage timing, queue health, and the readiness
        verdict — the one document every surface (health.json snapshot,
        health CLI, HTTP probes) serves."""
        workers = {}
        for sup in (self._pre_sup, self._predict_sup, self._write_sup):
            if sup is not None:
                workers[sup.name] = sup.health()
        running = bool(workers) and all(
            w["state"] in (SupervisedThread.STARTING,
                           SupervisedThread.RUNNING,
                           SupervisedThread.RESTARTING)
            for w in workers.values())
        try:
            queue_health = self.queue.health()
        except Exception as e:  # noqa: BLE001 — backend down ≠ probe down
            queue_health = {"backend": type(self.queue).__name__,
                            "reachable": False,
                            "error": f"{type(e).__name__}: {e}"}
        h = {"running": running,
             "draining": self._draining.is_set(),
             # staleness/restart detection (PR 4): a monotonically
             # increasing sequence lets orchestrators spot a frozen
             # snapshot file; pid + uptime reset on a silent restart
             "uptime_s": round(time.monotonic() - self._t_start, 3),
             "pid": os.getpid(),
             "snapshot_seq": next(self._snapshot_seq),
             # wall/monotonic clock pair (PR 13): spans carry monotonic
             # timestamps; the fleet trace collector normalizes each
             # replica's spans onto the wall clock through this pair
             "clock": {"wall": time.time(), "monotonic": time.monotonic()},
             # replica identity + failover counters (PR 5)
             "replica_id": self.replica_id,
             # version identity (PR 16): the registry version this replica
             # serves — None when unversioned.  Fleet aggregation reports
             # the version MIX across replicas (normal mid-rollout); the
             # canary judge compares replicas by it.
             "model_version": self.model_version,
             "heartbeat_age_s": round(self._heartbeat_age(), 3),
             "reclaimed": self.reclaimed,
             "duplicates": self.duplicates,
             "total_records": self.total_records,
             "dead_lettered": self.dead_lettered,
             "shed": self.shed,
             # zero cold start (PR 11): warm-up progress + the replica's
             # measured spawn-to-first-result — these ride the health doc
             # into fleet aggregation and FleetSignals
             "warmup": self.warmup_state(),
             "cold_start_s": (None if self._cold_start_s is None
                              else round(self._cold_start_s, 3)),
             # fused-dequant quantized predict (PR 14): what the model
             # serves with — 0 float, 8 int8 (W8A8), 4 int4 (W4A16)
             "quantized_bits": self._quantized_bits(),
             # resource accounting (PR 15): HBM decomposition (weights /
             # kv_state / executables + per-program exec counts) and the
             # per-process resource read — fleet-aggregated by
             # serving/fleet.py, scrapeable as serving_hbm_bytes /
             # process_* gauges
             "resources": self._resources_doc(),
             "process": self._process_doc(),
             # flight-recorder ring pressure (PR 15): a dropped count
             # means the ring is too small for the drain period
             "recorder": self.recorder.stats(),
             "breaker": self._breaker.health(),
             "dead_letter_breaker": self._dead_breaker.health(),
             # live data-plane knob targets (PR 10): the autoscaler's
             # fleet aggregation reads the fast tier's position from here
             "knobs": self.knobs(),
             "workers": workers,
             "stages": self.stage_metrics(),
             "queue": queue_health}
        if self._batcher is not None:
            # continuous batching (PR 12): slot occupancy + token counters
            # ride the health doc into fleet aggregation
            h["generation"] = self._batcher.stats()
        if self._slo is not None:
            # SLO attribution (PR 13): objective + windowed burn rate ride
            # the health doc so fleet aggregation / FleetSignals can
            # consume them without a separate scrape
            h["slo"] = self._slo.snapshot()
        # usage attribution (PR 19): cumulative per-tenant totals — fleet
        # aggregation sums these across replicas for `manager metrics`
        h["usage"] = self.meter.snapshot()
        if self._admission is not None:
            # overload armor (PR 17): admitted/rejected tallies the fleet
            # aggregation sums, and the per-reason split for triage
            h["admission"] = self._admission.snapshot()
        if self._brownout is not None:
            # the ladder stage (fleet-merged as MAX) + transition history
            # — what incident bundles show for "when did we degrade"
            h["brownout"] = self._brownout.snapshot()
        if self._faults.any_active:
            # fault injection (PR 16): an armed replica must be visible
            # from the outside — never silently chaotic
            h["faults"] = self._faults.describe()
        h["ready"] = self._readiness(h)
        return h

    def _readiness(self, h: Dict) -> Dict:
        """/readyz verdict derived from an already-computed health doc."""
        reasons = []
        if h["draining"]:
            reasons.append("draining")
        if not h["running"]:
            reasons.append("workers-not-running")
        w = h.get("warmup") or {}
        if w.get("state") in ("pending", "warming"):
            # a cold replica must not take routed traffic: every record it
            # claims pays a compile the warm fleet members would not.
            # `failed`/`degraded` do NOT hold readiness — the lazy-compile
            # path still serves, just cold.
            reasons.append(
                f"warming ({w.get('compiled', 0)}/{w.get('total', 0)} "
                f"programs)")
        if h["breaker"]["state"] == CircuitBreaker.OPEN:
            reasons.append("result-write-breaker-open")
        q = h["queue"]
        if not q.get("reachable", True):
            reasons.append("backend-unreachable")
        rb = q.get("read_breaker")
        if rb is not None and rb["state"] == CircuitBreaker.OPEN:
            reasons.append("read-breaker-open")
        cap = self.params.ready_queue_depth
        if cap is None:
            cap = q.get("max_depth")
        depth = q.get("depth", -1)
        if cap is not None and depth >= 0 and depth >= cap:
            reasons.append(f"queue-depth {depth} >= {cap}")
        if self._faults.readyz_active:
            # fault point (PR 16): hold readiness for the configured
            # uptime — exercises the rollout's wait-for-ready timeout
            fr = self._faults.readyz_block_reason(h["uptime_s"])
            if fr:
                reasons.append(fr)
        return {"ready": not reasons, "reasons": reasons}

    def ready(self) -> Dict:
        """Readiness probe document (`/readyz`).  While the AOT warm-up
        set is compiling the verdict is not-ready with a
        ``warming (k/n programs)`` reason, and the progress block rides
        the body so operators see WHY a new replica is not taking traffic
        yet."""
        h = self.health()
        doc = dict(h["ready"])
        if self._warm_state.get("state") != "off":
            doc["warmup"] = {
                k: self._warm_state.get(k)
                for k in ("state", "compiled", "total", "seconds")}
        return doc

    @staticmethod
    def metrics_from_health(h: Dict) -> Dict:
        """The `/metrics` JSON document derived from a health() document —
        shared with `manager metrics`, which only has the snapshot file."""
        e2e = h["stages"]["e2e"]
        doc = {"served": h["total_records"],
               "quarantined": h["dead_lettered"],
               "shed": h["shed"],
               "restarts": sum(w["restart_count"]
                               for w in h["workers"].values()),
               "queue_depth": h["queue"].get("depth", -1),
               "dead_letters": h["queue"].get("dead_letters", -1),
               "breaker_trips": h["breaker"]["trip_count"],
               "stages": h["stages"],
               "latency_ms": {"p50": e2e["p50_ms"], "p99": e2e["p99_ms"]}}
        if isinstance(h.get("admission"), dict):
            doc["admitted"] = h["admission"].get("admitted", 0)
            doc["rejected"] = h["admission"].get("rejected", 0)
        if isinstance(h.get("brownout"), dict):
            doc["brownout_stage"] = h["brownout"].get("stage", 0)
        return doc

    def metrics(self) -> Dict:
        """Flat JSON counters + the per-stage timing breakdown (`/metrics`)
        — byte-compatible with the PR 2/3 document; the Prometheus rendering
        of the same registry lives on `prom_metrics()`."""
        return self.metrics_from_health(self.health())

    def prom_metrics(self) -> str:
        """Prometheus text exposition v0.0.4 of this engine's registry
        (`/metrics?format=prom`)."""
        return self.registry.to_prometheus()

    def export_trace(self, path: str) -> str:
        """Dump the tracer's span ring as Chrome trace-event JSON (open in
        Perfetto / chrome://tracing, or summarize with
        `tools/trace_view.py`)."""
        return self.tracer.export_chrome_trace(path)

    def shutdown(self, drain_s: Optional[float] = None,
                 close_admission: bool = True):
        """Stop serving.  With ``drain_s`` (graceful drain, PR 2): close
        admission on the queue, flip `/readyz` to ``draining`` so probes
        stop routing traffic, let the workers finish the stream backlog and
        flush every staged AND dispatched in-flight batch, then join —
        falling back to a hard stop when the budget runs out.  Without it:
        immediate stop (the PR 1 behaviour).

        ``close_admission=False`` (PR 10) is the SCALE-DOWN drain: this
        replica stops claiming new work and flushes what it holds, but the
        shared queue stays open — N-replica deployments must not have one
        retiring replica cut off ingest for the survivors (the autoscaler
        and ``manager scale N`` retire replicas this way)."""
        if drain_s is None:
            drain_s = 0.0
        self._event("shutdown", drain_s=drain_s,
                    retire=not close_admission)
        sups = (self._pre_sup, self._predict_sup, self._write_sup)
        started = any(s is not None for s in sups)
        if drain_s > 0 and started:
            self._draining.set()
            if close_admission:
                try:
                    self.queue.close_admission()
                except Exception:  # noqa: BLE001 — backend down: drain
                    pass           # anyway
            else:
                self._retiring.set()
            wait_until(lambda: not any(
                s is not None and s.is_alive() for s in sups), drain_s)
        # the compat aliases (_pre_thread/_thread) point at the SAME thread
        # objects the supervisors own — joining the supervisors covers them
        self._stop.set()
        for sup in sups:
            if sup is not None:
                sup.join(timeout=5)
        if self._pre_pool is not None:
            self._pre_pool.shutdown(wait=False)
            self._pre_pool = None
        if self._http is not None:
            self._http.stop()
            self._http = None
        # deregister this engine's callback gauges: a stopped engine must
        # not contribute stale samples to (or be kept alive by) a registry
        # it shares with live engines; idempotent across repeat shutdowns
        for gauge, fn in self._gauge_fns:
            gauge.remove_function(fn)
        self._gauge_fns = []
        # drop this replica's heartbeat series entirely (scale-down): a
        # stopped replica must not linger in the exposition as a frozen or
        # zero "age", which would read as perfectly fresh
        self._hb_gauge.remove(replica=self.replica_id)
        # release cached shm-ring attachments (PR 7): a long-lived engine
        # serving successive shm-lane producers must not hold their
        # (unlinked) segments mapped forever.  close() is view-safe — a
        # mapping with live exported buffers survives the attempt — and a
        # later shm record simply re-attaches by name.
        try:
            _wire.detach_all()
        except Exception:  # noqa: BLE001 — cleanup is best-effort
            pass
        if self._tb is not None:
            self._tb.flush()
