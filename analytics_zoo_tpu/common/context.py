"""Runtime/context bootstrap — the TPU-native analog of NNContext.

Reference parity: `NNContext.initNNContext` (common/NNContext.scala:133-186) and the
Python `init_nncontext`/`init_spark_on_local` family (pyzoo/zoo/common/nncontext.py:23-127)
bootstrap a SparkContext + BigDL Engine (node/core discovery).  On TPU the "cluster" is a
device mesh: this module discovers JAX devices, builds a `jax.sharding.Mesh`, and holds the
process-wide configuration (default dtypes, RNG seed, mesh axis layout) that every other
subsystem reads.  There is no py4j bridge and no engine reflection — the context is a plain
Python object.
"""

from __future__ import annotations

import dataclasses
import math
import os
import threading
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical mesh axis names.  Data parallelism is always present; the other axes are
# length-1 unless explicitly requested (green-field beyond the reference, which only has DP
# — SURVEY.md §2.3 "parallelism strategies").
DATA_AXIS = "data"
MODEL_AXIS = "model"      # tensor parallelism
PIPE_AXIS = "pipe"        # pipeline parallelism
SEQ_AXIS = "seq"          # sequence/context parallelism
EXPERT_AXIS = "expert"    # expert parallelism (MoE)

ALL_AXES = (DATA_AXIS, MODEL_AXIS, PIPE_AXIS, SEQ_AXIS, EXPERT_AXIS)


@dataclasses.dataclass
class ZooConf:
    """Unified typed config tree.

    Replaces the reference's 4-way config sprawl (SparkConf keys, Java system properties,
    scopt CLI, serving YAML — SURVEY.md §5 config).  One dataclass, overridable from
    environment variables prefixed ``ZOO_TPU_`` (e.g. ``ZOO_TPU_SEED=7``).
    """

    seed: int = 42
    # Compute dtype for matmuls/convs (MXU-friendly); params stay in param_dtype.
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # Mesh layout: axis name -> size.  -1 for data means "all remaining devices".
    mesh_axes: Tuple[str, ...] = (DATA_AXIS,)
    mesh_shape: Tuple[int, ...] = (-1,)
    # Training-loop behaviour
    failure_retry_times: int = 5          # bigdl.failure.retryTimes analog
    # backoff base between checkpoint-restore retries (common/resilience.py
    # RetryPolicy drives the schedule; a crashed device/runtime gets a
    # breather instead of an immediate hot-loop restore)
    failure_retry_backoff_s: float = 0.1
    checkpoint_keep: int = 3
    log_every_n_steps: int = 10
    # Data layer
    prefetch_buffers: int = 2             # double-buffered device infeed
    # Profiling: directory for jax.profiler traces; empty = disabled.  Also
    # switchable via ZOO_TPU_PROFILE=1 (traces land in ./zoo_tpu_profile).
    profile_dir: str = ""
    # Multi-host (multi-process) bootstrap — the TPU-pod analog of the
    # reference's Spark cluster deploy (wp-bigdl.md:160-164 scaling story).
    # coordinator_address non-empty => jax.distributed.initialize() is called
    # by init_context before device discovery; every process then sees the
    # GLOBAL device set and the mesh spans the pod.  num_processes/process_id
    # default to -1 = let JAX infer from the TPU runtime (on Cloud TPU the
    # runtime knows); set both explicitly for CPU/GPU clusters.
    coordinator_address: str = ""
    num_processes: int = -1
    process_id: int = -1

    @classmethod
    def from_env(cls, **overrides) -> "ZooConf":
        conf = cls(**overrides)
        for f in dataclasses.fields(conf):
            env_key = "ZOO_TPU_" + f.name.upper()
            if env_key in os.environ and f.name not in overrides:
                raw = os.environ[env_key]
                if f.default is not dataclasses.MISSING:
                    default = f.default
                elif f.default_factory is not dataclasses.MISSING:
                    default = f.default_factory()
                else:
                    continue
                if isinstance(default, bool):
                    setattr(conf, f.name, raw.lower() in ("1", "true", "yes"))
                elif isinstance(default, int):
                    setattr(conf, f.name, int(raw))
                elif isinstance(default, (tuple, list)):
                    # comma-separated: ZOO_TPU_MESH_AXES=data,model
                    # ZOO_TPU_MESH_SHAPE=-1,2 (ints where the default is ints)
                    parts = [p.strip() for p in raw.split(",") if p.strip()]
                    if default and all(isinstance(d, int) for d in default):
                        parts = [int(p) for p in parts]
                    setattr(conf, f.name, type(default)(parts))
                elif isinstance(default, (str, float)):
                    setattr(conf, f.name, type(default)(raw))
                # other field types (dicts, objects) are not env-parseable: skip
        if os.environ.get("ZOO_TPU_PROFILE", "").lower() in ("1", "true", "yes") \
                and not conf.profile_dir:
            conf.profile_dir = "zoo_tpu_profile"
        return conf


def global_put(leaf, sharding):
    """device_put that also works when the sharding spans processes
    (multi-host pods): device_put cannot target non-addressable devices, so
    each process fills only its addressable shards from the (identical)
    host value via make_array_from_callback.  Single shared implementation
    for ZooContext.global_device_put and ShardingPlan.shard."""
    if jax.process_count() == 1:
        return jax.device_put(leaf, sharding)
    a = np.asarray(leaf)
    return jax.make_array_from_callback(a.shape, sharding,
                                        lambda idx: a[idx])


class ZooContext:
    """Process-wide runtime context: devices, mesh, seed, dtype policy."""

    def __init__(self, conf: Optional[ZooConf] = None,
                 devices: Optional[Sequence[jax.Device]] = None):
        self.conf = conf or ZooConf.from_env()
        # before the first compile of the job: a cold ResNet-50 / BERT
        # compile is paid once per cache directory, not once per process
        from analytics_zoo_tpu.inference.aot import enable_persistent_cache
        enable_persistent_cache()
        self.devices = list(devices if devices is not None else jax.devices())
        self.mesh = self._build_mesh()
        self._rng = jax.random.PRNGKey(self.conf.seed)
        self._lock = threading.Lock()

    # -- mesh ---------------------------------------------------------------
    def _build_mesh(self) -> Mesh:
        axes = list(self.conf.mesh_axes)
        shape = list(self.conf.mesh_shape)
        n = len(self.devices)
        fixed = int(np.prod([s for s in shape if s > 0])) if shape else 1
        if -1 in shape:
            if n % fixed != 0:
                raise ValueError(
                    f"device count {n} not divisible by fixed mesh dims {fixed}")
            shape[shape.index(-1)] = n // fixed
        used = int(np.prod(shape))
        if used > n:
            raise ValueError(f"mesh shape {shape} needs {used} devices, have {n}")
        dev_array = np.asarray(self.devices[:used]).reshape(shape)
        return Mesh(dev_array, tuple(axes))

    @property
    def num_devices(self) -> int:
        return int(np.prod(self.mesh.devices.shape))

    @property
    def data_parallel_size(self) -> int:
        return self.mesh.shape.get(DATA_AXIS, 1)

    # -- multi-host topology --------------------------------------------------
    @property
    def process_count(self) -> int:
        """Processes participating in THIS context's mesh — not
        jax.process_count(): a context built over jax.local_devices() in a
        multi-process world (e.g. a process-local AutoML trial,
        MultiProcessSearchEngine) is single-host from the Estimator's point
        of view, and must not split batches or take collective paths
        (round 5 fix — the old global count silently halved the feed batch
        of process-local trials)."""
        return len({d.process_index for d in self.devices})

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def is_multi_host(self) -> bool:
        return self.process_count > 1

    def local_devices(self):
        return [d for d in self.devices
                if d.process_index == jax.process_index()]

    def global_device_put(self, tree, sharding):
        """Place a host-local pytree under a (possibly cross-process) sharding
        (see `global_put`: every process holds the same host value and fills
        only its addressable shards)."""
        return jax.tree.map(lambda a: global_put(a, sharding), tree)

    # -- sharding helpers ---------------------------------------------------
    def data_sharding(self, batch_rank: int = 1) -> NamedSharding:
        """Sharding that splits the leading (batch) axis over the data axis."""
        spec = P(DATA_AXIS, *([None] * (batch_rank - 1)))
        return NamedSharding(self.mesh, spec)

    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharding_for(self, shape,
                           token_len: Optional[int] = None) -> NamedSharding:
        """Sharding for one batch array: leading axis over `data`, and — when
        the mesh has a seq axis > 1 (sequence-parallel training) — the second
        (token) axis over `seq`, provided axis 1 IS the token axis:
        ``token_len`` (the model input's axis-1 length, passed by the
        Estimator feed) must match and divide evenly.  Divisibility alone is
        not enough (ADVICE r5): a (B, C) one-hot label with C % n_seq == 0
        must stay data-sharded, not silently resharded as if it carried
        tokens.  Arrays whose axis 1 doesn't match (labels, weights) stay
        data-sharded only; ops/attention.py then rides the ring for the
        sharded activations."""
        rank = len(shape)
        axes = [DATA_AXIS] + [None] * (rank - 1)
        n_seq = self.mesh.shape.get(SEQ_AXIS, 1)
        if (rank >= 2 and n_seq > 1 and token_len is not None
                and shape[1] == token_len and shape[1] % n_seq == 0
                and shape[1] > 1):
            axes[1] = SEQ_AXIS
        return NamedSharding(self.mesh, P(*axes))

    # -- rng ----------------------------------------------------------------
    def next_rng(self) -> jax.Array:
        with self._lock:
            self._rng, sub = jax.random.split(self._rng)
            return sub

    def set_seed(self, seed: int) -> None:
        with self._lock:
            self.conf.seed = seed
            self._rng = jax.random.PRNGKey(seed)


_global_ctx: Optional[ZooContext] = None
_ctx_lock = threading.Lock()


def init_context(conf: Optional[ZooConf] = None, *, mesh_axes=None, mesh_shape=None,
                 devices=None, seed: Optional[int] = None) -> ZooContext:
    """Initialise (or re-initialise) the global ZooContext.

    Analog of `NNContext.initNNContext` / `init_nncontext` — but instead of spinning up a
    JVM+Spark cluster it discovers TPU devices and lays them out in a mesh.
    """
    global _global_ctx
    conf = conf or ZooConf.from_env()
    if mesh_axes is not None:
        conf.mesh_axes = tuple(mesh_axes)
    if mesh_shape is not None:
        conf.mesh_shape = tuple(mesh_shape)
    if seed is not None:
        conf.seed = seed
    if conf.coordinator_address:
        _ensure_distributed(conf)
    with _ctx_lock:
        _global_ctx = ZooContext(conf, devices=devices)
        return _global_ctx


_distributed_initialized = False


def _ensure_distributed(conf: ZooConf) -> None:
    """Multi-process bootstrap (idempotent): after this, jax.devices() is the
    GLOBAL device set and collective programs span all processes.  The analog
    of the reference's cluster Engine init (NNContext.scala:133-186 +
    wp-bigdl's parameter-server bootstrap); on TPU pods the runtime already
    knows the topology, so only the coordinator address is required."""
    global _distributed_initialized
    if _distributed_initialized:
        return
    kw = {"coordinator_address": conf.coordinator_address}
    if conf.num_processes >= 0:
        kw["num_processes"] = conf.num_processes
    if conf.process_id >= 0:
        kw["process_id"] = conf.process_id
    jax.distributed.initialize(**kw)
    _distributed_initialized = True


# API-parity alias (pyzoo/zoo/common/nncontext.py:23)
init_nncontext = init_context


def get_context() -> ZooContext:
    global _global_ctx
    with _ctx_lock:
        if _global_ctx is None:
            _global_ctx = ZooContext()
        return _global_ctx


def mesh() -> Mesh:
    return get_context().mesh
