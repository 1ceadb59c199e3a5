"""Ahead-of-time compile warm-up for serving replicas (PR 11 tentpole).

A fresh replica used to pay a full XLA trace+compile the first time each
power-of-two bucket arrived — the PR 10 chaos bench had to pre-warm buckets
by hand so cold compiles would not read as SLO violations, and the
autoscaler's scale-up decisions actuated a compile-time late.  This module
makes cold start a *derived, measured* path:

- ``warmup_manifest(model, ...)`` enumerates every program a deployment can
  hit — one entry per ``(bucket, dtype, scales-variant)`` over the mesh
  placement in force — straight from the same ``_bucket`` ladder
  ``do_predict``/``dispatch`` use (including the non-pow-2 ``max_batch``
  clamp and the PR 6 mesh-multiple rounding), so the warm-up set is exactly
  the serve-time compile set, not a guess.
- ``warm_up(model, manifest)`` compiles each entry via
  ``jax.jit(...).lower().compile()`` and parks the executable in the
  model's AOT cache, which ``do_predict``/``dispatch``/
  ``_jitted_with_scales`` consult BEFORE tracing — a warmed bucket is never
  traced again, and a ``_jitted_scaled_base`` rebuild cannot invalidate it
  (the cache is keyed by load epoch + signature, not wrapper identity).
- ``enable_persistent_cache()`` wires jax's persistent compilation cache —
  the ONE place in the tree that does — at the directory
  ``compile_cache_dir`` resolves (``$JAX_COMPILATION_CACHE_DIR``, else a
  deployment's own setting, else one fixed path inside the checkout):
  the *second* replica of a topology, and the second run of a training
  job, load executables from disk instead of compiling at all.
- ``COMPILE_STATS`` counts what actually happened via jax's monitoring
  events: compile REQUESTS (fired whether the persistent cache answers or
  not) and persistent-cache hits/misses — with every program cacheable,
  ``cache_misses`` is the true backend-compile count, so "the warm path
  performs zero XLA compiles" is a tested number, not a hope.
- ``compile_recorded(fn, args, program, cause)`` (PR 37) is ``lower`` +
  ``compile`` with a record of how it went — the walls of both, jax's own
  seconds inside them (attributed through ``COMPILE_STATS.making``), the
  persistent cache's verdict and the executable's bytes: what
  ``ContinuousBatcher._compiled`` keeps for every scheduler program and
  ``warmup_state()["programs"]`` shows.

Single-input models only (the serving engine stacks one tensor per record);
multi-input ``do_predict`` callers still go through the same AOT cache,
they just warm lazily on first use.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger(__name__)


class WarmupEntry(NamedTuple):
    """One compiled program of the warm-up set.  ``shape`` is the
    per-record tail shape (the batch axis is ``bucket``); ``scales`` marks
    the int8-wire variant that dequantizes on device with per-row scales;
    ``mesh``/``sharding`` record the placement the program is lowered
    against (informational — the model's live mesh is what the compile
    actually uses)."""

    bucket: int
    shape: Tuple[int, ...]
    dtype: str                       # numpy dtype str of the wire batch
    scales: bool
    mesh: Optional[Tuple[int, int]]  # (data, model) axes, None = single-chip
    sharding: str                    # off | batch | tensor | hybrid
    # quantized-weight program variant (PR 14): "float" | "w8" | "w4" —
    # informational like mesh/sharding (the model's live params decide what
    # the compile lowers against), but it makes the quantized program set
    # explicit in `manager warmup` output and pins the manifest derivation
    # to the graph actually deployed
    variant: str = "float"


# jax's monitoring keys this module reads (jax 0.9.0), by the field of
# ``CompileStats`` / of a program's record their seconds are summed into
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_MLIR = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_SECONDS = {_TRACE: "trace_s", _MLIR: "mlir_s", _BACKEND: "backend_s",
            _RETRIEVAL: "retrieval_s"}


class CompileStats:
    """Process-wide XLA compile accounting, fed by jax's monitoring
    events.  ``compile_requests`` counts trips into
    ``compile_or_get_cached`` (the ``backend_compile_duration`` event
    wraps the whole call on this jax, so it fires even when the
    persistent cache serves the binary — it measures how often the
    tracing layer ASKED for an executable, and its seconds include cache
    retrieval).  ``cache_hits``/``cache_misses`` count persistent-cache
    traffic once a cache dir is configured: with every program cacheable
    (see ``enable_persistent_cache``), **``cache_misses`` IS the true
    backend-compile count** — the warm path asserts it stays zero.

    ``making(record)`` (PR 37) attributes the calling thread's events to
    one program's record (``compile_recorded``): jax fires them
    synchronously on the thread inside ``lower()`` / ``compile()``, so a
    thread-local is enough although the warm-up thread and a serving
    thread may both be compiling.  Beside the backend's seconds a record
    takes the stages this object keeps no sum of: ``trace_s`` (Python
    tracing to a jaxpr) and ``mlir_s`` (jaxpr to StableHLO), OUTERMOST
    events only — jax times every nested ``jit`` it traces (``jnp.matmul``
    is one) inside its caller's event, so a plain sum would count the same
    seconds at every depth — and ``retrieval_s`` (a persistent-cache hit's
    read, deserialisation and load onto the device; inside the backend's
    seconds)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()     # .record, .depth
        self.compile_requests = 0
        self.compile_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {"compile_requests": self.compile_requests,
                    "compile_seconds": round(self.compile_seconds, 3),
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses}

    @contextlib.contextmanager
    def making(self, record: Dict):
        """Until exit, this thread's events also count into ``record``
        (``trace_s``, ``mlir_s``, ``backend_s``, ``retrieval_s``,
        ``cache``)."""
        local = self._local
        outer = getattr(local, "record", None)
        local.record, local.depth = record, 0
        try:
            yield record
        finally:
            local.record = outer

    def _scalar(self, key: str, value, **kw) -> None:
        # jax announces a timed stage's START as a scalar under the
        # stage's own key: the depth of trace / lowering on this thread
        # while it makes a program
        if key == _TRACE or key == _MLIR:
            local = self._local
            if getattr(local, "record", None) is not None:
                local.depth += 1

    def _event(self, key: str, **kw) -> None:
        if key == _CACHE_HIT:
            verdict = "hit"
        elif key == _CACHE_MISS:
            verdict = "miss"
        else:
            return
        with self._lock:
            if verdict == "hit":
                self.cache_hits += 1
            else:
                self.cache_misses += 1
        record = getattr(self._local, "record", None)
        if record is not None and record["cache"] != "miss":
            record["cache"] = verdict

    def _duration(self, key: str, dur: float, **kw) -> None:
        field = _SECONDS.get(key)
        if field is None:
            return
        dur = float(dur)
        local = self._local
        record = getattr(local, "record", None)
        if record is not None:
            if key == _TRACE or key == _MLIR:
                local.depth = depth = max(local.depth - 1, 0)
                if not depth:       # else: inside the outer event's seconds
                    record[field] += dur
            else:
                record[field] += dur
        if key == _BACKEND:
            with self._lock:
                self.compile_requests += 1
                self.compile_seconds += dur
            # incident flight recorder (PR 15): compile requests are
            # first-class forensic events — "the replica was compiling"
            # explains a stall better than any latency histogram; since
            # PR 37 with WHICH program, for whom, and whether the
            # persistent cache had it
            made = record or {}
            try:
                from analytics_zoo_tpu.common.observability import (
                    get_recorder)
                get_recorder().record(
                    "compile", seconds=round(dur, 4),
                    program=made.get("program"), cause=made.get("cause"),
                    cache=made.get("cache"))
            except Exception:  # noqa: BLE001 — diagnostics only
                pass


COMPILE_STATS = CompileStats()
_LISTENERS_INSTALLED = False
_INSTALL_LOCK = threading.Lock()


def install_compile_listeners() -> CompileStats:
    """Register the monitoring listeners feeding ``COMPILE_STATS``
    (idempotent; jax keeps listeners for the process lifetime)."""
    global _LISTENERS_INSTALLED
    with _INSTALL_LOCK:
        if _LISTENERS_INSTALLED:
            return COMPILE_STATS
        from jax._src import monitoring
        monitoring.register_event_listener(COMPILE_STATS._event)
        monitoring.register_event_duration_secs_listener(
            COMPILE_STATS._duration)
        monitoring.register_scalar_listener(COMPILE_STATS._scalar)
        _LISTENERS_INSTALLED = True
    return COMPILE_STATS


# The cache directory used when neither the environment nor the deployment
# names one: a FIXED path inside the checkout (git-ignored).  The directory
# is part of how a cache is found again, so it is never derived from a
# pid, a pidfile, a timestamp or mkdtemp.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def compile_cache_dir(configured: Optional[str] = None) -> Optional[str]:
    """Where this process keeps its persistent compilation cache.

    1. ``$JAX_COMPILATION_CACHE_DIR`` when set — jax itself reads it, and
       then nothing in code names another directory (``configured`` does
       not apply, not even ``"off"``).
    2. else ``configured`` — a deployment's ``params.compile_cache_dir``
       (``"off"`` = no persistent cache, returns None), or the empty
       directory a cold-start A/B measures against.
    3. else ``DEFAULT_COMPILE_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if configured == "off":
        return None
    return configured or DEFAULT_COMPILE_CACHE_DIR


def enable_persistent_cache(configured: Optional[str] = None
                            ) -> Optional[str]:
    """Turn on jax's persistent compilation cache at
    ``compile_cache_dir(configured)`` and drop the min-compile-time /
    min-entry-size thresholds so EVERY program lands in it — the serving
    bucket programs are individually small and fast to compile, exactly
    what the default thresholds skip.  Process-global (jax.config) and
    idempotent; serving replicas, ``manager warmup`` and the training
    bootstrap (``ZooContext``) all come through here.  A caller with no
    setting of its own (``configured=None``) never moves a choice made
    earlier in the process: a directory already in force stays, and a
    deployment's ``"off"`` (jax's own ``jax_enable_compilation_cache``
    switch) stays off.  Returns the directory, or None when off."""
    import jax
    path = compile_cache_dir(configured)
    if path is None:
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    current = jax.config.jax_compilation_cache_dir
    if configured is None and current:
        path = current
    if current != path:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        logger.info("aot: persistent XLA compilation cache at %s", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    install_compile_listeners()
    return path


def bucket_ladder(max_batch: int, multiple: int = 1,
                  model_cap: Optional[int] = None) -> List[int]:
    """Every bucket ``_bucket(n, cap, multiple)`` can produce for
    ``1 <= n <= max_batch`` — the exact compile set a deployment serving
    batches up to ``max_batch`` walks through.  ``model_cap`` is the
    model's (pow-2-clamped) ``max_batch`` ceiling; the engine's adaptive
    batcher never reads more than its own ``max_batch`` records, so the
    ladder stops at the smaller of the two."""
    from analytics_zoo_tpu.inference.inference_model import _bucket
    cap = int(model_cap) if model_cap is not None else int(max_batch)
    seen = []
    n = 1
    while n <= max(1, int(max_batch)):
        b = _bucket(n, cap, multiple)
        if b not in seen:
            seen.append(b)
        if n >= max_batch:
            break
        n = min(n * 2, int(max_batch))
    return sorted(seen)


def infer_input_spec(model) -> Optional[Tuple[Tuple[int, ...], str]]:
    """Best-effort per-record input spec ``(tail_shape, dtype)`` from the
    loaded topology's declared input shape (Sequential/Model builders
    carry it); None when the model does not declare one — the caller must
    then supply an explicit spec."""
    inner = getattr(model, "_model", None)
    shape = getattr(inner, "_declared_input_shape", None)
    if shape is None:
        return None
    try:
        return tuple(int(s) for s in shape), "<f4"
    except (TypeError, ValueError):
        return None


def warmup_manifest(model, input_shape=None, dtype: str = "<f4",
                    max_batch: Optional[int] = None,
                    scales: str = "auto",
                    scale_dtypes: Sequence[str] = ("|i1",)
                    ) -> List[WarmupEntry]:
    """Derive the warm-up set for ``model`` as deployed: one entry per
    ``(bucket, dtype, scales-variant)`` over the placement in force.

    ``input_shape``/``dtype`` describe ONE record on the wire (default:
    the topology's declared input shape, f32).  ``max_batch`` is the
    engine's adaptive-batcher ceiling (default: the model's own pow-2
    ``max_batch``); buckets come from the same ladder ``do_predict`` pads
    to, so the mesh-multiple rounding and the non-pow-2 clamp are
    reproduced, not re-implemented.  ``scales``: ``"off"`` plain-only,
    ``"both"`` every bucket per scale dtype (plus the plain entry),
    ``"auto"``/``"on"`` = scale variants when the program is jit-compiled
    (the int8 wire is part of the serving surface), plain-only for bridge
    models.  ``scale_dtypes`` names the compact wire dtypes the scale
    variants arrive in — default the int8 wire; deployments serving u8
    images (``QuantizedTensor(uint8, 1.0)`` records) add ``"|u1"`` via
    the spec so their per-row-scale program warms too."""
    if input_shape is None:
        spec = infer_input_spec(model)
        if spec is None:
            raise ValueError(
                "warmup_manifest: the model declares no input shape; pass "
                "input_shape=(d0, ...) for one record")
        input_shape, dtype = spec
    tail = tuple(int(s) for s in input_shape)
    multiple = int(getattr(model, "_batch_multiple", 1) or 1)
    cap = int(getattr(model, "max_batch", 1024) or 1024)
    mb = int(max_batch) if max_batch else cap
    mesh = None
    mode = getattr(model, "_sharding_mode", None) or "off"
    m = getattr(model, "_mesh", None)
    if m is not None:
        mesh = (int(m.shape.get("data", 1)), int(m.shape.get("model", 1)))
    jit_ok = hasattr(getattr(model, "_jitted", None), "lower")
    if scales in ("auto", "on"):
        want_scales = jit_ok
    elif scales == "both":
        want_scales = True
    else:
        want_scales = False
    # quantized-weight deployments (PR 14): the manifest enumerates the
    # SAME (bucket, dtype, scales) surface, but every program lowers
    # against the quantized graph — stamp the variant so the warm set is
    # explicit about which program family it compiled (do_quantize bumps
    # the AOT epoch, so float and quantized executables can never mix)
    try:
        from analytics_zoo_tpu.inference.quantize import quantized_bits
        variant = {8: "w8", 4: "w4"}.get(
            quantized_bits(getattr(model, "_params", None) or {}), "float")
    except Exception:  # noqa: BLE001 — exotic bridge params
        variant = "float"
    entries: List[WarmupEntry] = []
    for bucket in bucket_ladder(mb, multiple, model_cap=cap):
        entries.append(WarmupEntry(bucket, tail, np.dtype(dtype).str,
                                   False, mesh, mode, variant))
        if want_scales:
            # compact-wire variants: the batch arrives in its wire dtype
            # with per-row dequant scales (engine QuantizedTensor path)
            for sdt in scale_dtypes:
                entries.append(WarmupEntry(bucket, tail,
                                           np.dtype(sdt).str, True,
                                           mesh, mode, variant))
    return entries


class GenWarmupEntry(NamedTuple):
    """One program of a generation deployment's warm-up set (PR 12
    continuous batching): the scheduler runs one ``prefill`` program per
    (admission-batch, prompt-bucket, lane), one ``insert`` per
    (admission-batch, lane), and one ``decode_step`` per lane — the
    (prefill-bucket x decode-step) set a warm replica must hold to serve
    its first token with zero compiles."""

    kind: str                        # prefill | decode_step | insert |
    #                                  paged_prefill | paged_shared |
    #                                  paged_decode
    prefill_bucket: Optional[int]    # prompt padding bucket (prefill only)
    lane_bucket: int                 # decode lane capacity bucket
    prefill_batch: Optional[int] = None   # admission batch bucket (pow-2)
    prefix_blocks: Optional[int] = None   # prefix-table bucket
    #                                       (paged_shared only)


def generation_manifest(prefill_buckets: Sequence[int],
                        lane_buckets: Sequence[int],
                        prefill_batches: Sequence[int] = (1,),
                        cache_model: bool = True,
                        paged: bool = False,
                        prefix_blocks: Sequence[int] = ()
                        ) -> List[GenWarmupEntry]:
    """Enumerate the continuous-batching program set: for every decode
    lane, its step program, plus — per admission-batch bucket — one
    insert program and one prefill program per prompt bucket.  The ONE
    enumeration shared by ``ContinuousBatcher.warm`` and the serving
    warm-up manifest, so the pre-warm pass compiles exactly the set the
    scheduler will look up.  ``cache_model=True`` keeps only prompt
    buckets that fit the lane (prefill allocates the KV cache at lane
    capacity, so bigger prompts can never run there); bare-state models
    (lane capacity is not a prompt bound — the scheduler pads any
    admissible prompt to any bucket of the ladder) keep them all.

    ``paged=True`` (PR 18) swaps the set for the paged-pool programs:
    one ``paged_decode`` per lane, one ``paged_prefill`` (prompt forward
    + block commit, no separate insert) per (batch, prompt bucket), and
    — when prefix sharing is on (``prefix_blocks`` non-empty) — one
    ``paged_shared`` per (batch, suffix bucket, prefix-table bucket)."""
    entries: List[GenWarmupEntry] = []
    for lane in sorted({int(b) for b in lane_buckets}):
        if paged:
            entries.append(GenWarmupEntry("paged_decode", None, lane))
            for bb in sorted({int(b) for b in prefill_batches}):
                for pb in sorted({int(b) for b in prefill_buckets}):
                    if pb > lane and cache_model:
                        continue
                    entries.append(GenWarmupEntry(
                        "paged_prefill", pb, lane, bb))
                    for npb in sorted({int(b) for b in prefix_blocks}):
                        entries.append(GenWarmupEntry(
                            "paged_shared", pb, lane, bb, npb))
            continue
        entries.append(GenWarmupEntry("decode_step", None, lane))
        for bb in sorted({int(b) for b in prefill_batches}):
            entries.append(GenWarmupEntry("insert", None, lane, bb))
            for pb in sorted({int(b) for b in prefill_buckets}):
                if pb <= lane or not cache_model:
                    entries.append(GenWarmupEntry("prefill", pb, lane, bb))
    return entries


def resolve_manifest(model, warmup_spec) -> List[WarmupEntry]:
    """Manifest from a ``ServingParams.warmup`` value: ``True`` derives
    everything from the model, a spec dict ``{"shape", "dtype", "scales",
    "max_batch"}`` overrides per key — the ONE resolution shared by the
    serving engine and ``manager warmup`` so the pre-warm pass compiles
    exactly the set the replicas will look up."""
    spec = warmup_spec if isinstance(warmup_spec, dict) else {}
    return warmup_manifest(
        model,
        input_shape=spec.get("shape"),
        dtype=str(spec.get("dtype", "<f4")),
        max_batch=spec.get("max_batch"),
        scales=str(spec.get("scales", "auto")),
        scale_dtypes=tuple(spec.get("scale_dtypes") or ("|i1",)))


def warm_error(entry, exc: BaseException) -> str:
    """One failed warm-up entry as the short string the stats carry."""
    return f"{entry}: {type(exc).__name__}: {exc}"[:500]


def warm_pass(manifest: Sequence, warm_one, progress=None, stop=None,
              who: str = "aot") -> Dict:
    """One pass over a warm-up manifest, for either plane: the loop and
    the stats document ``warm_up`` and ``ContinuousBatcher.warm`` share.
    ``warm_one(entry)`` compiles one entry's program and returns False
    when it was there already; ``progress(done, total, entry)`` is called
    after each entry; ``stop()`` true ends the pass (a draining engine
    must not keep the process alive compiling programs nobody will run).

    Returns ``{"programs", "compiled", "skipped", "failed", "errors",
    "stopped", "seconds", "compile_stats"}`` where ``compile_stats`` is
    the COMPILE_STATS delta for the pass — on a process whose persistent
    cache is already populated, ``cache_misses`` stays 0 and
    ``cache_hits`` covers the set (the zero-cold-start evidence)."""
    install_compile_listeners()
    before = COMPILE_STATS.snapshot()
    t0 = time.monotonic()
    compiled = skipped = failed = 0
    errors: List[str] = []
    stopped = False
    for i, entry in enumerate(manifest):
        if stop is not None and stop():
            stopped = True
            break
        try:
            fresh = warm_one(entry)
            compiled += 1 if fresh else 0
            skipped += 0 if fresh else 1
        except Exception as e:  # noqa: BLE001 — one bad entry must not
            # strand the rest of the set; it is counted, and its message
            # rides the stats so `degraded` says WHAT failed
            failed += 1
            errors.append(warm_error(entry, e))
            logger.warning("%s: warm-up entry %s failed", who, entry,
                           exc_info=True)
        if progress is not None:
            progress(i + 1, len(manifest), entry)
    after = COMPILE_STATS.snapshot()
    return {"programs": len(manifest), "compiled": compiled,
            "skipped": skipped, "failed": failed, "errors": errors,
            "stopped": stopped,
            "seconds": round(time.monotonic() - t0, 3),
            "compile_stats": {k: round(after[k] - before[k], 3)
                              for k in after}}


def warm_up(model, manifest: Optional[Sequence[WarmupEntry]] = None,
            progress=None, stop=None, **manifest_kw) -> Dict:
    """Compile every program in ``manifest`` (default: derived via
    ``warmup_manifest``) into the model's AOT executable cache.  Each
    entry that is already cached (an earlier warm-up, or a live request
    that beat us to it) is skipped for free.  ``progress(done, total,
    entry)`` is called after each entry — the serving engine uses it to
    publish per-bucket progress on ``/readyz``.  Returns ``warm_pass``'s
    stats document."""
    if manifest is None:
        manifest = warmup_manifest(model, **manifest_kw)
    stats = warm_pass(
        manifest,
        lambda entry: model.warm(entry.bucket, entry.shape,
                                 dtype=entry.dtype, scales=entry.scales),
        progress=progress, stop=stop)
    logger.info("aot: warm-up %d program(s) in %.2fs (%d fresh, %d cached, "
                "%d failed; %s backend compile(s), %s cache hit(s))",
                stats["programs"], stats["seconds"], stats["compiled"],
                stats["skipped"], stats["failed"],
                stats["compile_stats"]["cache_misses"],
                stats["compile_stats"]["cache_hits"])
    return stats


# -- one program's start-up record (PR 37) --------------------------------------

_MEMORY_FIELDS = (("code_bytes", "generated_code_size_in_bytes"),
                  ("alias_bytes", "alias_size_in_bytes"))


def compile_recorded(fn, args, program: str, cause: str
                     ) -> Tuple[object, Dict]:
    """``fn.lower(*args).compile()`` and the record of how it went:
    ``program`` and ``cause`` as given (``"warmup"``, or ``"request"``
    when a serving thread had to make the program: a stall with a name),
    ``t`` (start, ``time.monotonic()``), ``lower_s`` (wall of ``lower``:
    Python tracing + lowering to StableHLO), ``compile_s`` (wall of
    ``compile``: a backend compile on a persistent-cache miss, retrieval
    + load on a hit), ``cache`` (``hit`` / ``miss`` / ``off``), jax's own
    seconds inside those two (``trace_s`` + ``mlir_s`` within
    ``lower_s``; ``backend_s`` within ``compile_s``, ``retrieval_s``
    within that: see ``CompileStats``), and of the executable's
    ``memory_analysis()`` ``code_bytes`` (the generated code: what the
    persistent cache has to hold) and ``alias_bytes`` (what the program
    takes over in place of its arguments; 0 where the backend reports
    none)."""
    record = {"program": program, "cause": cause, "t": time.monotonic(),
              "lower_s": 0.0, "compile_s": 0.0, "cache": "off",
              "trace_s": 0.0, "mlir_s": 0.0, "backend_s": 0.0,
              "retrieval_s": 0.0}
    with COMPILE_STATS.making(record):
        lowered = fn.lower(*args)
        t_lowered = time.monotonic()
        exe = lowered.compile()
        t_compiled = time.monotonic()
    record["lower_s"] = t_lowered - record["t"]
    record["compile_s"] = t_compiled - t_lowered
    memory = exe.memory_analysis()
    for field, attr in _MEMORY_FIELDS:
        record[field] = int(getattr(memory, attr, 0) or 0)
    return exe, record


def startup_totals(records: Sequence[Dict]) -> Dict[str, float]:
    """The flat sums over program records that ``ContinuousBatcher.stats()``
    publishes.  ``startup_s.<stage>``: the WARM-UP's seconds by stage — a
    program made for a request (``startup_n.late`` counts them) keeps its
    seconds in its record: they lie inside the stall it caused, and a
    start's stages must not count them a second time.  ``startup_n.*``
    and ``startup_b.code`` (generated code in all) are over every program
    made."""
    warmed = [r for r in records if r["cause"] == "warmup"]
    totals = {"startup_s." + field[:-2]: sum(r[field] for r in warmed)
              for field in ("lower_s", "compile_s", "trace_s", "mlir_s",
                            "backend_s", "retrieval_s")}
    totals.update({
        "startup_n.programs": len(records),
        "startup_n.cache_hits": sum(r["cache"] == "hit" for r in records),
        "startup_n.cache_misses": sum(r["cache"] == "miss"
                                      for r in records),
        "startup_n.late": len(records) - len(warmed),
        "startup_b.code": sum(r["code_bytes"] for r in records)})
    return totals
