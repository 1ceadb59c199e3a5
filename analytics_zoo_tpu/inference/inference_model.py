"""InferenceModel — the multi-backend concurrent-inference holder.

Reference parity: pipeline/inference/InferenceModel.scala:30-889 — loaders for multiple
model formats + a blocking queue of weight-sharing model clones for concurrent predict
(modelQueue, :67,741-790).

TPU-native redesign: a jitted predict function IS thread-safe and weight-sharing —
no clone queue needed; concurrency is handled by XLA's stream executor.  What remains is
(a) the loader surface: zoo weights (`do_load`), TF SavedModel (`do_load_tensorflow`,
via the interop bridge — the TFNet analog), ONNX when available, and (b) **bucketed
batching**: inputs are padded to the nearest power-of-two batch so a handful of compiled
programs serve any request size (the serving-latency answer to the reference's per-core
BLAS threading, SURVEY.md §7 hard-parts).

Sharded multi-chip serving (PR 6): `shard()` places the parameters over a
`data` x `model` device mesh once (ShardingPlan.shard) and commits every
padded batch with a batch-axis NamedSharding before dispatch, so the GSPMD
partitioner runs the SAME jitted program over all chips — batch-sharded for
small models (replicated params), megatron tensor-sharded for large
transformer stacks — and XLA overlaps the ICI transfers with compute.  The
pow-2 buckets become mesh-aware: rounded up to a multiple of the batch-axis
size so every device gets an equal slice and the compile cache stays one
program per bucket.
"""

from __future__ import annotations

import logging
import os
import threading
import time
import weakref
from typing import Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from analytics_zoo_tpu.common.observability import get_startup
from analytics_zoo_tpu.nn.module import Layer

logger = logging.getLogger(__name__)


def _pow2_floor(n: int) -> int:
    return 1 << (max(1, int(n)).bit_length() - 1)


def _bucket(n: int, max_batch: int, multiple: int = 1) -> int:
    """Power-of-two bucket for an n-row batch, rounded UP to a multiple of
    `multiple` (the mesh batch-axis size) so padded batches shard evenly
    over the data axis; `max_batch` is a pow-2 multiple of `multiple`
    (InferenceModel clamps/validates), so buckets stay pow-2."""
    b = 1
    while b < n and b < max_batch:
        b *= 2
    b = min(b, max_batch)
    if multiple > 1 and b % multiple != 0:
        b = min(-(-b // multiple) * multiple, max(max_batch, multiple))
    return b


def _pad_to_bucket(xs: List[np.ndarray], scales, n: int, bucket: int):
    """Zero-pad the batch arrays (and per-row scales, padded with ones)
    from ``n`` rows up to the pow-2 ``bucket``.  The ONE padding
    implementation shared by `do_predict` and `dispatch`, so both paths
    produce identical padded signatures and hit one compile cache."""
    if n < bucket:
        xs = [np.concatenate(
            [a, np.zeros((bucket - n,) + a.shape[1:], a.dtype)])
            for a in xs]
    if scales is None:
        return xs, None
    sc = np.concatenate([np.asarray(scales, np.float32),
                         np.ones((bucket - n,), np.float32)])
    return xs, sc


class _LazyPending:
    """Deferred-call result handle (`dispatch` oversized-batch fallback):
    the work happens at ``result()``, matching `_Pending`'s interface."""

    def __init__(self, fn: Callable):
        self._fn = fn

    def result(self):
        return self._fn()


class InferenceModel:
    """supported_concurrent_num is the concurrency CONTRACT
    (InferenceModel.scala:33,67: a queue of N weight-sharing clones): here it
    bounds (a) how many predict() callers may dispatch simultaneously (a
    semaphore replaces the clone queue — the jitted program is already
    weight-sharing and thread-safe) and (b) how many batches a single
    predict() keeps IN FLIGHT on the device before reading results back —
    JAX dispatch is async, so host-side padding/decode of batch k+1..k+N
    overlaps device compute of batch k."""

    def __init__(self, supported_concurrent_num: int = 2,
                 max_batch: int = 1024, registry=None):
        # the bucket ladder is pow-2 by contract: a non-pow-2 max_batch
        # would add a non-pow-2 TERMINAL bucket (e.g. 100 after 64),
        # silently doubling the compile-cache footprint per signature —
        # clamp DOWN to the nearest power of two instead
        mb = max(1, int(max_batch))
        self.max_batch = _pow2_floor(mb)
        if self.max_batch != mb:
            logger.warning(
                "InferenceModel: max_batch=%d is not a power of two; "
                "clamping to %d so the bucket ladder stays pow-2 (a "
                "non-pow-2 terminal bucket doubles the compile cache)",
                mb, self.max_batch)
        self.concurrent_num = max(1, int(supported_concurrent_num))
        # sharded multi-chip serving (PR 6): populated by shard()
        self._mesh = None                 # jax.sharding.Mesh when sharded
        self._plan = None                 # the params ShardingPlan in force
        self._sharding_mode: Optional[str] = None   # batch|tensor|hybrid
        self._batch_multiple = 1          # mesh data-axis size (bucket quantum)
        self._sharded_calls = 0           # batches committed to the mesh
        self._mesh_gauge = None           # (gauge, provider) registration
        self._predict_fn: Optional[Callable] = None
        self._params = None
        self._state = None
        self._model: Optional[Layer] = None
        self._jitted = None
        self._sem = threading.BoundedSemaphore(self.concurrent_num)
        # AOT executable cache (PR 11 zero cold start): one compiled
        # program per (load epoch, padded signature), consulted by
        # do_predict/dispatch BEFORE tracing.  aot.warm_up pre-populates
        # it at load time; live misses compile once and join it.  The
        # epoch bumps whenever the underlying program changes (re-load,
        # quantize, shard) so stale executables can never serve — and a
        # `_jitted_scaled_base` wrapper rebuild alone can NOT invalidate
        # it (the old churn: every rebuild emptied the jit cache).
        self._aot: Dict = {}
        self._aot_lock = threading.Lock()
        self._aot_epoch = 0
        self.aot_hits = 0               # padded calls served by the cache
        self.aot_compiles = 0           # lower().compile() calls we made
        # per-program execution counters (PR 15 resource accounting):
        # label -> executions, keyed the way the warm-up manifest names
        # programs (bucket x tail-shape / dtype [+scales]) so "which
        # program is actually hot" reads straight off the health doc
        self._aot_execs: Dict[str, int] = {}
        self.load_seconds: Optional[float] = None   # last do_load* wall
        self.load_mmap = False          # last load used the mmap store
        # scaled-program wrappers per base program (bounded): a base that
        # drifts A -> B -> A (instance patches, chaos shims) re-uses A's
        # wrapper and its jit cache instead of rebuilding from scratch
        self._scaled_wrappers: Dict = {}
        # unified telemetry (PR 4): predict/dispatch latency + batch-size
        # histograms.  `registry` is an observability.MetricsRegistry; left
        # None it binds lazily — to the serving engine's registry when this
        # model is handed to a ClusterServing (re-bound per engine, so a
        # model reused across engines follows the live one), else the
        # process-wide one.  An EXPLICIT registry is pinned: engines won't
        # re-bind it.
        self._obs_registry = registry
        self._obs_registry_explicit = registry is not None
        self._obs = None

    def bind_registry(self, registry) -> bool:
        """Adopt `registry` for the predict/dispatch histograms — called by
        a ClusterServing at construction so one scrape covers the whole
        data plane.  A model constructed with an EXPLICIT registry stays
        pinned (returns False); otherwise the model follows the most recent
        binder (a model reused across engines, e.g. bench --sweep, reports
        into the live engine's scrape) and the cached histogram handles are
        dropped so they re-create in the new registry."""
        if self._obs_registry_explicit:
            return False
        self._obs_registry = registry
        self._obs = None
        return True

    def _observe(self, method: str, n: int, dt_s: float) -> None:
        """Record one predict/dispatch call: wall latency and batch size,
        labeled by entry point (`do_predict` blocks on readback; `dispatch`
        measures enqueue-to-device only) and by the sharding mode in force
        (`off` single-chip, `batch`/`tensor`/`hybrid` over the mesh)."""
        if self._obs is None:
            from analytics_zoo_tpu.common.observability import get_registry
            reg = self._obs_registry or get_registry()
            self._obs_registry = reg
            self._obs = (
                reg.histogram("inference_predict_seconds",
                              "Model predict/dispatch wall latency",
                              labels=("method", "sharding")),
                reg.histogram("inference_batch_size",
                              "Records per predict/dispatch call",
                              labels=("method",),
                              buckets=tuple(float(1 << i)
                                            for i in range(12))))
            # the mesh-devices provider holds only a WEAK ref to the model
            # (models have no shutdown hook, and a registry — possibly the
            # process-global one — must not keep a discarded model's params
            # alive); the previous registration is dropped on re-bind so
            # stale providers don't pile up in old registries
            if self._mesh_gauge is not None:
                old_gauge, old_fn = self._mesh_gauge
                old_gauge.remove_function(old_fn)
            self_ref = weakref.ref(self)

            def _mesh_devices_provider() -> float:
                model = self_ref()
                return float(model.mesh_devices) if model is not None else 1.0

            gauge = reg.gauge("inference_mesh_devices",
                              "Devices in the serving mesh (1 = single-chip)")
            gauge.set_function(_mesh_devices_provider)
            self._mesh_gauge = (gauge, _mesh_devices_provider)
        sharding = self._sharding_mode or "off"
        self._obs[0].labels(method=method, sharding=sharding).observe(dt_s)
        self._obs[1].labels(method=method).observe(float(n))

    # -- sharded multi-chip serving (PR 6 tentpole) ---------------------------
    @property
    def mesh_devices(self) -> int:
        """Devices the sharded predict spans (1 = single-chip)."""
        if self._mesh is None:
            return 1
        return int(np.prod(self._mesh.devices.shape))

    def _mesh_matches(self, req) -> bool:
        """Does a shard() mesh request describe the placement already in
        force?  (int = device count, tuple = (data, model) axes, Mesh =
        identity)."""
        from jax.sharding import Mesh
        if isinstance(req, Mesh):
            return req is self._mesh
        shape = self._mesh.shape
        if isinstance(req, (tuple, list)):
            return (len(req) == 2
                    and int(req[0]) == int(shape.get("data", 1))
                    and int(req[1]) == int(shape.get("model", 1)))
        return int(req) == self.mesh_devices

    def mesh_info(self) -> Dict:
        """Mesh topology + structural-evidence counters (serving_bench A/B:
        on CPU sim the win is asserted from these, not wall clock)."""
        if self._mesh is None:
            return {"devices": 1, "sharding": "off", "sharded_calls": 0}
        return {"devices": self.mesh_devices,
                "sharding": self._sharding_mode,
                "axes": {k: int(v) for k, v in self._mesh.shape.items()},
                "sharded_calls": self._sharded_calls}

    def shard(self, mesh=None, sharding: str = "auto", plan=None):
        """Route predict/dispatch through a sharded program over a device
        mesh: parameters are placed ONCE (`ShardingPlan.shard`), every
        padded batch is committed with a batch-axis `NamedSharding`, and the
        jitted program partitions via GSPMD — batch-sharded for small models
        (replicated params), megatron tensor-sharded for large transformer
        stacks, `sharding="auto"` choosing by parameter count.

        `mesh` may be None (all devices), an int (first N devices), a
        `(data, model)` shape tuple (hybrid layouts), or a prebuilt
        `jax.sharding.Mesh`.  Idempotent: a model already sharded keeps its
        mesh (bench replicas share one model across N engines).  On CPU,
        simulate with XLA_FLAGS=--xla_force_host_platform_device_count=N."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from analytics_zoo_tpu.parallel import sharding as shardlib
        mode = sharding or "auto"
        if mode == "off":
            return self
        if mode not in ("auto", "batch", "tensor"):
            raise ValueError(f"sharding={mode!r}: expected one of "
                             "auto|batch|tensor|off")
        if self._jitted is None:
            raise RuntimeError("load a model first")
        if not hasattr(self._jitted, "lower"):
            raise ValueError(
                "sharded serving needs a jax-native model; bridge predict "
                "functions (TF SavedModel via TFNet) cannot be partitioned")
        if self._mesh is not None:
            if mode not in ("auto", self._sharding_mode):
                logger.warning(
                    "InferenceModel: already sharded %s over %d devices; "
                    "ignoring shard(sharding=%r) — one placement per load",
                    self._sharding_mode, self.mesh_devices, mode)
            elif mesh is not None and not self._mesh_matches(mesh):
                logger.warning(
                    "InferenceModel: already sharded over %d device(s) %s; "
                    "ignoring the conflicting mesh=%r — one placement per "
                    "load (re-load the model to re-shard)",
                    self.mesh_devices, dict(self._mesh.shape), mesh)
            return self
        if isinstance(mesh, Mesh):
            m = mesh
        elif isinstance(mesh, (tuple, list)):
            m = shardlib.serving_mesh(shape=tuple(mesh))
        else:
            if mode == "auto":
                mode = shardlib.serving_mode_for(self._params)
            m = shardlib.serving_mesh(n_devices=mesh, mode=mode)
        dd = int(m.shape.get("data", 1))
        mm = int(m.shape.get("model", 1))
        if mode == "auto":
            mode = "hybrid" if (dd > 1 and mm > 1) else \
                ("tensor" if mm > 1 else "batch")
        if dd > 1 and self.max_batch % dd != 0:
            if isinstance(mesh, (Mesh, tuple, list)):
                # the caller chose this layout explicitly: reject with an
                # attainable fix (max_batch is pow-2 by construction, so
                # "raise max_batch" can never make a non-pow-2 axis divide)
                raise ValueError(
                    f"mesh data axis {dd} does not divide max_batch="
                    f"{self.max_batch}; choose a power-of-2 data axis")
            # auto-built batch mesh over a non-pow-2 device count (3, 6,
            # 12 chips): use the largest batch axis that divides the pow-2
            # max_batch instead of refusing to shard at all
            usable = min(_pow2_floor(dd), self.max_batch)
            logger.warning(
                "InferenceModel: %d visible device(s) do not divide the "
                "pow-2 max_batch=%d; sharding over the largest usable "
                "batch axis (%d device(s)) instead", dd, self.max_batch,
                usable)
            m = shardlib.serving_mesh(n_devices=usable, mode="batch")
            dd, mm = usable, 1
        if plan is None:
            if mode == "batch":
                # batch mode is an explicit contract: params replicated,
                # ONLY the batch splits — even for models the auto
                # heuristic would tensor-shard
                plan = shardlib.replicated_plan()
            else:
                # tensor/hybrid: the caller (or auto's size gate) decided,
                # so skip the parameter-count threshold
                plan = shardlib.serving_plan(self._params, m,
                                             min_tensor_params=0)
                if not plan.rules:
                    logger.warning(
                        "InferenceModel: tensor sharding requested but no "
                        "parameter leaf matches the megatron plan; params "
                        "stay replicated (inputs still shard over the "
                        "batch axis)")
        self._params = plan.shard(self._params, m)
        if self._state:
            self._state = jax.tree.map(
                lambda a: jax.device_put(a, NamedSharding(m, P())),
                self._state)
        self._mesh = m
        self._plan = plan
        self._sharding_mode = mode
        self._batch_multiple = max(1, dd)
        self._bump_epoch()     # committed shardings change the programs
        self._obs = None       # histogram children re-label with the mode
        logger.info(
            "InferenceModel: sharded predict enabled — mode=%s mesh=%dx%d "
            "(data x model) over %d device(s)", mode, dd, mm,
            self.mesh_devices)
        return self

    def _commit(self, xs: List, scales):
        """Commit one padded batch (and its per-row scales) to the mesh with
        the batch NamedSharding: device_put is asynchronous, so the ICI/PCIe
        transfer of batch k+1 overlaps batch k's compute.  Single-chip mode
        passes host arrays straight through (jit transfers them itself)."""
        if self._mesh is None:
            return xs, scales
        from jax.sharding import NamedSharding, PartitionSpec as P
        m = self._mesh
        xs = [jax.device_put(
            a, NamedSharding(m, P("data", *([None] * (a.ndim - 1)))))
            for a in xs]
        if scales is not None:
            # int8 wire path: the per-row dequant scales ride the same
            # batch axis as their rows
            scales = jax.device_put(scales, NamedSharding(m, P("data")))
        self._sharded_calls += 1
        return xs, scales

    # -- AOT executable cache (PR 11 zero cold start) -------------------------
    def _bump_epoch(self) -> None:
        """Invalidate every compiled executable: the underlying program
        changed (new weights, quantized graph, mesh placement)."""
        with self._aot_lock:
            self._aot_epoch += 1
            self._aot.clear()
            self._scaled_wrappers.clear()
            self._aot_execs.clear()    # counts name the OLD epoch's programs

    def _aot_key(self, fn, xs: List, sc, multi: bool):
        # `fn` (the jitted base or its per-base scaled wrapper) is part of
        # the key: an external `_jitted` patch that skips the epoch bump
        # must MISS, never serve the old program — while the per-base
        # wrapper cache keeps the fn identity stable across scaled/
        # unscaled interleaving, so legitimate reuse still hits
        return (self._aot_epoch, fn, multi, sc is not None,
                tuple((tuple(a.shape), np.dtype(a.dtype).str) for a in xs))

    def _padded_call(self, xs: List, sc, multi: bool, execute: bool = True):
        """Run ONE padded, committed bucket batch — the single exec path
        shared by `do_predict`, `dispatch` and `warm`.  An AOT executable
        for this signature (warm-up or an earlier call) runs without any
        tracing; a miss lowers+compiles once via the same jitted program
        and joins the cache (hitting the persistent compilation cache when
        one is configured), so at most one compile per signature per load
        epoch ever happens, no matter how wrappers churn.

        ``execute=False`` (the warm-up path) stops after the executable
        exists: compiling is what warm-up buys — running every program on
        a dummy batch would burn real forward-pass CPU against the live
        pipeline for nothing."""
        if sc is not None:
            fn = self._jitted_with_scales()
            if not hasattr(fn, "lower"):
                # host bridge path (TFNet lambda): nothing to compile
                return fn(self._params, self._state, xs[0], sc) \
                    if execute else None
            args = (self._params, self._state, xs[0], sc)
        else:
            fn = self._jitted
            if not hasattr(fn, "lower"):
                return fn(self._params, self._state,
                          xs if multi else xs[0]) if execute else None
            args = (self._params, self._state, xs if multi else xs[0])
        key = self._aot_key(fn, xs, sc, multi)
        exe = self._aot.get(key)
        if exe is None:
            # compile OUTSIDE the lock: the warm-up thread walks its
            # manifest through here, and a live request racing it for a
            # different bucket must not queue behind the whole set.  Two
            # threads racing the SAME signature both compile (the
            # persistent cache makes the loser cheap) and the dict keeps
            # whichever registered first.
            exe = fn.lower(*args).compile()
            with self._aot_lock:
                self.aot_compiles += 1
                if key[0] == self._aot_epoch:
                    exe = self._aot.setdefault(key, exe)
        else:
            self.aot_hits += 1
        if execute:
            label = self.program_label(xs, scales=sc)
            with self._aot_lock:
                self._aot_execs[label] = self._aot_execs.get(label, 0) + 1
            return exe(*args)
        return None

    @staticmethod
    def program_label(xs: List, scales=None) -> str:
        """Human-stable program name matching the warm-up manifest entry
        naming: ``b<bucket>x<tail shape>/<dtype>[+scales]``."""
        a = xs[0]
        tail = "x".join(str(int(s)) for s in a.shape[1:]) or "scalar"
        label = (f"b{int(a.shape[0])}x{tail}/"
                 f"{np.dtype(a.dtype).str}")
        return label + "+scales" if scales is not None else label

    def aot_memory_bytes(self) -> Optional[int]:
        """Best-effort total generated-code size of the cached AOT
        executables (the ``executables`` HBM component of the resource
        ledger).  None when this jax/backend exposes no memory analysis —
        the count is still exact either way."""
        total, seen = 0, 0
        with self._aot_lock:
            exes = list(self._aot.values())
        for exe in exes:
            try:
                ma = exe.memory_analysis()
                total += int(getattr(ma, "generated_code_size_in_bytes",
                                     0) or 0)
                seen += 1
            except Exception:  # noqa: BLE001 — backend without analysis
                continue
        return total if seen else None

    def warm(self, bucket: int, shape, dtype: str = "<f4",
             scales: bool = False) -> bool:
        """Compile (or confirm cached) the program for one warm-up entry:
        a `(bucket,) + shape` batch of `dtype`, optionally the int8-wire
        per-row-scales variant.  Runs the REAL padded/committed exec path
        so the cached executable is byte-for-byte the one `do_predict` and
        `dispatch` will look up — but does NOT execute it (execute=False:
        `.compile()` returning IS the warm state).  Returns True when this
        call compiled a fresh executable, False when already cached."""
        x = np.zeros((int(bucket),) + tuple(int(s) for s in shape),
                     np.dtype(dtype))
        sc = np.ones((int(bucket),), np.float32) if scales else None
        xs, sc = self._commit([x], sc)
        fn = self._jitted_with_scales() if sc is not None else self._jitted
        if not hasattr(fn, "lower"):
            # bridge path (TFNet lambda): nothing compilable exists, so
            # nothing can become "fresh" — reporting True forever would
            # make warm_up claim compile progress that never happened
            return False
        fresh = self._aot_key(fn, xs, sc, False) not in self._aot
        self._padded_call(xs, sc, False, execute=False)
        return fresh

    def aot_stats(self) -> Dict:
        """AOT-cache evidence counters (bench/test surface) + the
        per-program execution counts (PR 15): which compiled program is
        actually serving traffic, keyed by its manifest-style label."""
        with self._aot_lock:
            return {"epoch": self._aot_epoch,
                    "cached_programs": len(self._aot),
                    "hits": self.aot_hits,
                    "compiles": self.aot_compiles,
                    "programs": dict(self._aot_execs)}

    # -- loaders --------------------------------------------------------------
    def do_load_model(self, model: Layer, params=None, state=None):
        """Load an in-memory zoo layer/container (doLoadBigDL analog).
        Re-loading resets any mesh placement — call `shard()` again for the
        new weights."""
        self._model = model
        if params is None and hasattr(model, "_params"):
            params, state = model._params, model._state
        self._params, self._state = params, state
        self._jitted = jax.jit(
            lambda p, s, x: model.apply(p, s, x, training=False)[0])
        self._mesh = None
        self._plan = None
        self._sharding_mode = None
        self._batch_multiple = 1
        self._bump_epoch()
        get_startup().stamp("model_loaded")
        return self

    def do_load(self, topology_builder: Callable[[], Layer],
                weights_path: str):
        """Rebuild topology via `topology_builder` and load weights from
        `.npz` (doLoad analog — weights file + known architecture).  A
        DIRECTORY path is an mmap'd weight store (inference/weightstore.py,
        PR 11): leaves restore as memory-mapped views — no deserialization
        copy at boot, and N replicas on one host share the page cache —
        then move to the device with one `jax.device_put` per leaf."""
        t0 = time.perf_counter()
        if os.path.isdir(weights_path):
            return self.do_load_store(topology_builder, weights_path)
        model = topology_builder()
        model.init_weights()
        model.load_weights(weights_path)
        out = self.do_load_model(model, model._params, model._state)
        self.load_seconds = time.perf_counter() - t0
        self.load_mmap = False
        return out

    def do_load_store(self, topology_builder: Callable[[], Layer],
                      store_dir: str):
        """Restore weights from an mmap'd store directory (PR 11 zero cold
        start): each leaf is a bare `.npy` read with
        ``np.load(mmap_mode="r")`` — the boot touches no weight bytes until
        the device transfer pages them in, and every replica on the host
        maps the SAME page-cache pages — then the whole tree is placed with
        `jax.device_put` once, so predict calls never re-transfer host
        params."""
        from analytics_zoo_tpu.inference import weightstore
        t0 = time.perf_counter()
        model = topology_builder()
        # the restore needs only the tree SKELETON (paths + shapes), not
        # computed weights: eval_shape traces init abstractly — no random
        # generation, no initializer compiles — shaving the warm boot
        # further.  Builders whose init resists abstract evaluation fall
        # back to a real init.
        try:
            p0, s0 = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            like = {"params": p0, "state": s0}
        except Exception:  # noqa: BLE001 — data-dependent init
            model.init_weights()
            like = {"params": model._params, "state": model._state}
        try:
            tree = weightstore.load_store(store_dir, like=like)
        except KeyError:
            # a QUANTIZED store (int8/int4 leaves + scales, PR 14) does not
            # match the float init skeleton — restore by the stored paths
            # (container names remapped onto the fresh model's auto-names,
            # shared leaves verified); layer lookup is key-based, so the
            # nested dicts slot straight in and predict serves quantized
            # from the mmap'd leaves.  The fallback is gated on the store
            # actually holding quantized leaves: a FLOAT store that failed
            # the keyed+positional match is corrupt or belongs to another
            # topology, and must keep failing loudly here, not at first
            # predict
            from analytics_zoo_tpu.inference.quantize import QUANT_LEAVES
            manifest = weightstore.read_manifest(store_dir) or {}
            names = {k.rsplit("/", 1)[-1]
                     for k in (manifest.get("leaves") or {})}
            if not names & set(QUANT_LEAVES):
                raise
            tree = weightstore.load_store_nested(store_dir, like=like)
            # paramless/stateless layers' empty {} slots produce no store
            # leaves; the executor still looks each one up — graft the
            # container skeleton from the template around the restored
            # leaves (params leaves may legitimately differ: {W_q4, s_g}
            # replace the skeleton's {W})
            tree["params"] = weightstore.graft_containers(
                like.get("params", {}), tree.get("params", {}),
                require_leaves=False)
            tree["state"] = weightstore.graft_containers(
                like.get("state", {}), tree.get("state", {}))
        params, state = tree["params"], tree["state"]
        # one transfer at load (vs one per predict for host-resident
        # params): DMA reads the mapped pages directly
        params = jax.device_put(params)
        if state:
            state = jax.device_put(state)
        model.set_weights(params, state)
        out = self.do_load_model(model, params, state)
        self.load_seconds = time.perf_counter() - t0
        self.load_mmap = True
        return out

    def do_load_tensorflow(self, saved_model_path: str,
                           signature: str = "serving_default"):
        """Wrap a TF SavedModel as the predict function (TFNet analog — see
        interop/tfnet.py; runs through the TF runtime bridge)."""
        from analytics_zoo_tpu.interop.tfnet import TFNet
        net = TFNet.from_saved_model(saved_model_path, signature=signature)
        self._model = net
        self._params, self._state = {}, {}
        self._jitted = lambda p, s, x: net.call({}, x)
        self._bump_epoch()
        return self

    def do_load_onnx(self, onnx_path: str):
        """ONNX model -> native predict function (reference: doLoadOpenVINO /
        onnx_loader.py ModelLoader; here via interop/onnx_loader.py)."""
        from analytics_zoo_tpu.interop.onnx_loader import load_onnx
        net = load_onnx(onnx_path)
        params = net.build(None, None)
        return self.do_load_model(net, params, {})

    def do_load_pytorch(self, model_or_path, example_input=None):
        """PyTorch model -> native predict function (reference: doLoadPyTorch,
        TorchNet.scala:39-242; here the TorchScript graph is imported into
        jnp via interop/torchnet.py — no libtorch at serve time)."""
        from analytics_zoo_tpu.interop.torchnet import TorchNet
        if isinstance(model_or_path, str):
            net = TorchNet(model_or_path)
        else:
            net = TorchNet.from_pytorch(model_or_path, example_input)
        params = net.build(None, None)
        return self.do_load_model(net, params, {})

    # -- quantization ----------------------------------------------------------
    def do_quantize(self, calib_inputs, force: bool = False, bits: int = 8,
                    group_size: int = 64,
                    percentile: Optional[float] = None):
        """Post-training weight quantization of the loaded model (the
        OpenVINO-int8 capability, pipeline/inference/OpenVinoInferenceSupportive
        .scala analog — served through the fused-dequant kernels in
        ops/quant_matmul.py).

        ``bits=8`` (W8A8): `calib_inputs` — one batch, a list of batches,
        or a `FeatureSet` (sampled via quantize.calibrate_featureset) —
        calibrates per-layer activation scales (`percentile` clips the
        range at that percentile of |x| instead of absmax); dense/conv
        weights become int8 with per-output-channel scales, ~4x less
        weight HBM per predict.  ``bits=4`` (W4A16): weight-only int4 with
        group-wise scales (`group_size` contraction rows per scale, two
        weights per byte, ~8x less weight HBM) — no calibration needed,
        `calib_inputs` may be None.

        OPT-IN on TPU v5e (re-measured 2026-07-30 round 5 with the
        LICM-proof timing loop, bench.py bench_resnet50_int8): raw
        s8xs8->s32 kernels reach only ~1.0-1.2x the bf16 rate through this
        XLA stack (tools/int8_matrix.py; bf16 already runs near the
        197 TF/s nameplate — int8 does NOT unlock a doubled MXU rate) — a
        COMPUTE-bound model quantizes for footprint, not speed; the win
        this path exists for is the MEMORY-bound serving regime (wide
        heads, decode steps), where weight bytes are the wall.  Accuracy
        parity holds (top-1 agreement 1.0 int8).  Pass force=True to
        quantize."""
        import warnings

        from analytics_zoo_tpu.inference.quantize import (
            _target_layers, quantize)
        if self._model is None:
            raise RuntimeError("load a model first")
        if not force:
            warnings.warn(
                "weight PTQ trades speed for HBM footprint on compute-bound "
                "models through this XLA stack (~0.84x end-to-end ResNet-50; "
                "raw-kernel matrix in tools/int8_matrix.py) — skipping "
                "quantization. Pass force=True to quantize anyway.",
                stacklevel=2)
            return self
        if not _target_layers(self._model, self._params or {}):
            # nothing quantizable (e.g. a TFNet-backed model whose predict
            # lambda must stay un-jitted) — leave the loaded path untouched
            return self
        self._params = quantize(self._model, self._params, self._state or {},
                                calib_inputs, bits=bits,
                                group_size=group_size, percentile=percentile)
        model = self._model
        self._jitted = jax.jit(
            lambda p, s, x: model.apply(p, s, x, training=False)[0])
        self._bump_epoch()     # the quantized graph is a new program
        if self._mesh is not None:
            # quantize rebuilt the params tree on host: re-place it under
            # the plan already in force (leaves whose new shapes no longer
            # divide fall back per _fit, with its one-time warning)
            self._params = self._plan.shard(self._params, self._mesh)
        return self

    # -- async dispatch (serving hot path, PR 3) ------------------------------
    class _Pending:
        """Handle for one async-dispatched batch: the jitted program is
        already enqueued on the device; ``result()`` blocks on the host
        transfer and strips the bucket padding."""

        def __init__(self, device_out, take: int):
            self._out = device_out
            self._take = take

        def result(self):
            take = self._take
            return jax.tree.map(lambda a: np.asarray(a)[:take], self._out)

    def dispatch(self, x, scales: Optional[np.ndarray] = None) -> "_Pending":
        """Dispatch ONE batch to the device without blocking on the host
        readback.  JAX dispatch is asynchronous, so the caller's next stage
        (preprocessing batch k+1, writing batch k-1's results) overlaps this
        batch's device compute; call ``.result()`` on the returned handle to
        transfer the outputs.  Pads to the same power-of-two bucket as
        `do_predict`, so the two paths share one compile cache.

        Unlike `do_predict` this takes no concurrency semaphore and does no
        internal chunking — callers (the serving engine's
        ``inflight_batches`` bound) cap how many handles they keep open; a
        batch larger than ``max_batch`` falls back to the chunking
        synchronous path, evaluated lazily at ``result()``."""
        if self._jitted is None:
            raise RuntimeError("load a model first")
        t0 = time.perf_counter()
        multi = isinstance(x, (list, tuple))
        if scales is not None and multi:
            raise ValueError("scales= supports single-input models only")
        xs = [np.asarray(a) for a in (x if multi else [x])]
        n = xs[0].shape[0]
        if n > self.max_batch:
            return _LazyPending(lambda: self.do_predict(x, scales=scales))
        bucket = _bucket(n, self.max_batch, self._batch_multiple)
        xs, sc = _pad_to_bucket(xs, scales, n, bucket)
        xs, sc = self._commit(xs, sc)
        out = self._padded_call(xs, sc, multi)
        self._observe("dispatch", n, time.perf_counter() - t0)
        return self._Pending(out, n)

    # -- predict --------------------------------------------------------------
    def _jitted_with_scales(self):
        """Lazily-built dequantizing predict: the int8/uint8 batch is
        TRANSFERRED in its compact dtype and multiplied by the per-row scale
        on device (round 5 serving wire path) — 4x less host->device
        traffic than shipping f32.

        Wrappers are cached PER BASE PROGRAM (PR 11 churn fix): the old
        single-slot cache was discarded whenever `_jitted` drifted, so a
        base that flipped A -> B -> A (instance patches, chaos shims,
        re-quantize round-trips) rebuilt the jit wrapper — and with it an
        empty compile cache — every flip.  Now each base keeps its wrapper
        (bounded; epoch bumps clear the table), and the AOT executable
        cache keys by signature rather than wrapper identity, so interleaved
        scaled/unscaled dispatches never recompile a bucket they have
        already paid for."""
        base = self._jitted
        fn = self._scaled_wrappers.get(base)
        if fn is not None:
            self._jitted_scaled, self._jitted_scaled_base = fn, base
            return fn
        import jax.numpy as jnp
        if hasattr(base, "lower"):        # a real jitted program

            def fn(p, s, x, sc):
                xf = x.astype(jnp.float32) \
                    * sc.reshape(sc.shape + (1,) * (x.ndim - 1))
                return base(p, s, xf)
            fn = jax.jit(fn)
        else:
            # un-jittable bridge path (e.g. TFNet lambda): dequantize on
            # host — correctness over the transfer win
            def fn(p, s, x, sc):
                xf = np.asarray(x, np.float32) * np.asarray(
                    sc, np.float32).reshape(
                        sc.shape + (1,) * (np.ndim(x) - 1))
                return base(p, s, xf)
        if len(self._scaled_wrappers) >= 8:
            # bounded: drop the oldest wrapper (its AOT executables stay
            # valid — they are keyed by signature, not by the wrapper)
            self._scaled_wrappers.pop(next(iter(self._scaled_wrappers)))
        self._scaled_wrappers[base] = fn
        # legacy aliases (pre-PR-11 callers/tests poked at these)
        self._jitted_scaled, self._jitted_scaled_base = fn, base
        return fn

    def do_predict(self, x, batch_size: Optional[int] = None,
                   scales: Optional[np.ndarray] = None) -> np.ndarray:
        """Batched forward with power-of-two bucket padding: at most
        log2(max_batch) compiled programs ever exist per input signature.
        Up to `supported_concurrent_num` batches stay in flight on the
        device before their (blocking) host readback.

        `scales` (round 5): per-row dequantization factors for a compact
        int8/uint8 `x` — the rows reach the device in their wire dtype and
        are dequantized there (single-input models only)."""
        if self._jitted is None:
            raise RuntimeError("load a model first")
        t0 = time.perf_counter()
        multi = isinstance(x, (list, tuple))
        if scales is not None and multi:
            raise ValueError("scales= supports single-input models only")
        xs = [np.asarray(a) for a in (x if multi else [x])]
        sc = None if scales is None else np.asarray(scales, np.float32)
        n = xs[0].shape[0]
        step = batch_size or self.max_batch
        outs = []
        pending: List = []   # (device result, take) not yet read back

        def drain_one():
            y, take = pending.pop(0)
            outs.append(jax.tree.map(lambda a: np.asarray(a)[:take], y))

        with self._sem:
            i = 0
            while i < n:
                take = min(step, n - i)
                bucket = _bucket(take, self.max_batch, self._batch_multiple)
                chunk = [a[i:i + take] for a in xs]
                chunk, schunk = _pad_to_bucket(
                    chunk, None if sc is None else sc[i:i + take],
                    take, bucket)
                chunk, schunk = self._commit(chunk, schunk)
                pending.append(
                    (self._padded_call(chunk, schunk, multi), take))
                if len(pending) >= self.concurrent_num:
                    drain_one()
                i += take
            while pending:
                drain_one()
        self._observe("do_predict", n, time.perf_counter() - t0)
        if isinstance(outs[0], (list, tuple)):
            return [np.concatenate([o[j] for o in outs])
                    for j in range(len(outs[0]))]
        return np.concatenate(outs)

    # reference-style aliases
    predict = do_predict
