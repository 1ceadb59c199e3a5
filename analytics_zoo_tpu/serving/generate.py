"""Continuous batching for autoregressive serving (PR 12 tentpole).

The serving engine was batch-in/batch-out end to end: a generation request
batch held every member hostage until the SLOWEST decode finished, and a
new request arriving one step after a batch dispatched waited a full
rollout.  This module is the token-level scheduler that fixes both — the
Orca (OSDI '22) / vLLM continuous-batching shape, built on the step-wise
decode API the generation models now expose
(``init_decode``/``decode_step``, models/seq2seq.py and
models/textmodels.py):

- **slot map** — decode runs over fixed ``(max_active, bucket)``-shaped
  state buffers ("lanes", one per pow-2 capacity bucket).  Requests CLAIM a
  free slot at a decode-step boundary (prefill via ``init_decode`` on a
  pow-2-padded prompt, inserted with ``.at[slot].set``), generate one token
  per step, and FREE the slot the moment they hit EOS / their token budget
  / their deadline — the freed slot is refilled at the next boundary, so
  one slow request never gates its neighbours.
- **compile-once programs** — every device program (one prefill per
  (prompt-bucket, lane), one decode step + one insert per lane) has a fixed
  shape, is compiled once through ``jax.jit(...).lower().compile()`` and
  cached; steady-state serving performs ZERO retraces no matter how
  requests churn (asserted via ``inference/aot.py`` ``COMPILE_STATS``).
  ``warm()`` pre-compiles the whole set from the same
  ``aot.generation_manifest`` the serving warm-up manifest carries, so a
  warm replica serves its first token with zero compiles.
- **the paged pool is the decode program's to overwrite** (PR 29) —
  ``pdecode`` DONATES the pool pytree, so a decode quantum updates the KV
  blocks in place instead of copying the whole pool first.  (``pprefill``
  and ``pshared`` do not yet, a known partial result: with the second
  pool's memory freed the TPU compiler emits the prefill programs ten
  times the size, which a capped compile cache cannot hold — ``PERF.md``
  sections 6 and 7.)  Every paged program is lowered
  from the pool's SHAPES (``lane.state_shapes``); the one ``exe(...)``
  call that takes the live pool and replaces ``lane.state`` with its
  output is the only code that ever passes it.  A call that fails after
  the runtime took the buffers leaves nothing to retry on:
  ``_rebuild_pool`` ends the lane's requests and starts from a zeroed
  pool.
- **one seam to the model** (PR 30) — a paged model's state is an
  opaque pytree here: the model creates it, commits prefills into it,
  decodes against it and sorts its bytes for the ledger
  (``_PAGED_CONTRACT``); its device format belongs to
  ``ops/paged_attention``.  The scheduler owns slots, blocks and time.
  Each paged program's argument tuple is written once (``_paged_args``),
  for lowering and for the call.
- **mesh placement** — lane state buffers are committed with a
  ``NamedSharding`` over the PR 6 serving mesh when the model is sharded
  (slot axis over ``data`` when it divides, replicated otherwise), so the
  decode step partitions like the rest of the predict plane.
- **events, not policy** — ``step()`` returns a list of ``GenEvent``s
  (first_token / partial / finish / shed / quarantine); the engine turns
  them into result writes, acks, quarantines and metrics, so the existing
  per-record contracts (tracing, deadlines, lease ack, dead-letter) ride
  unchanged.  A poisoned request (over-long or junk prompt, prefill
  failure) quarantines ITS SLOT only: rows are independent in every lane
  program, so neighbours' outputs are bitwise identical with or without
  the poison.
- **its own clock** (PR 25) — the generate thread's wall time is
  partitioned into ``PHASES`` by one ``PhaseClock`` (``self.clock``, shared
  with the engine's generate loop: loop and scheduler are one thread and
  one cycle), and every request is stamped where each step of its time to
  first token happens (submit -> admit -> first token -> first output).
  ``stats()`` carries both as cumulative sums.
- **its start on record** (PR 37) — ``_compiled`` is the one place a
  program is made, from ``warm()`` and from the hot path alike, and every
  program it makes leaves a record there (``program_records``: name,
  ``cause`` = ``warmup`` or ``request``, the seconds of lowering and
  compiling, the persistent cache's verdict, the executable's bytes);
  ``stats()`` carries their sums (``startup_s.*``, ``startup_n.*``,
  ``startup_b.*``) beside the process's start-up marks
  (``startup_t.<mark>``).  A looked-up program costs what it cost before.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.common.observability import PhaseClock, get_startup

logger = logging.getLogger(__name__)

# The generate thread's cycle, in the order a boundary runs it.  The engine
# owns idle / intake / bookkeep / flush, the scheduler the rest; the device
# is busy in the two *_wait phases (and from the dispatch on in admit).
PHASES = ("idle", "intake", "shed", "admit", "prefill_wait", "dispatch",
          "decode_wait", "fold", "bookkeep", "flush")
PHASE_SPAN_PREFIX = "zoo.gen."       # a phase's name on a profiler trace


def _pow2_ceil(n: int) -> int:
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


def _pow2_ladder(lo: int, hi: int) -> List[int]:
    """Pow-2 values in [lo, hi] (hi rounded up), smallest first."""
    out = []
    b = _pow2_ceil(lo)
    hi = _pow2_ceil(hi)
    while b <= hi:
        out.append(b)
        b *= 2
    return out


@dataclass
class GenerationParams:
    """``ServingParams.generation`` surface (config.yaml ``generation:``
    section).

    - ``max_active_slots`` — decode slots per lane: the in-flight batch
      width of the compiled decode-step program.
    - ``max_tokens`` — per-request generation budget (records may lower it
      via ``{"gen": {"max_tokens": n}}``, never raise it).
    - ``eos_id`` — stop token (None = budget-only stopping);
      ``start_id`` — first decoder token for encoder/decoder models whose
      prefill yields no logits (Seq2seq).
    - ``max_prompt_len`` — longest accepted prompt; longer quarantines.
    - ``bucket_lens`` — the pow-2 capacity ladder: one decode lane per
      value, a request lands in the smallest lane holding
      ``prompt + max_tokens``.  Default: one lane at
      ``pow2(max_prompt_len + max_tokens)``.
    - ``prefill_buckets`` — pow-2 prompt padding ladder (default 8 ..
      pow2(max_prompt_len)); one compiled prefill program per (bucket,
      lane) pair.
    - ``stream_interval`` — tokens between partial-result flushes
      (``OutputQueue`` partials / ``GET /v1/result`` tokens-so-far);
      0 disables streaming.
    - ``decode_quantum`` — tokens decoded per scheduler boundary: the
      decode program scans this many steps internally, so the per-call
      dispatch/sync overhead is paid once per ``decode_quantum`` tokens
      instead of per token (the CPU/host analog of GPU graph capture).
      Requests still join/leave at boundaries; a request finishing
      mid-quantum wastes at most ``decode_quantum - 1`` row-steps (its
      post-EOS tokens are discarded on host).  1 = pure per-token
      scheduling.
    - ``paged`` — paged KV mode (PR 18): KV lives in a fixed block POOL
      instead of per-slot monolithic lanes; each slot holds a block
      table, admission is bounded by free blocks, and prompts sharing a
      registered prefix share its resident pages.  Needs a model with
      the paged contract (``models/textmodels.TransformerLM``).
    - ``block_len`` — tokens per pool block (pow-2).
    - ``pool_blocks`` — usable pool blocks (default: enough for every
      slot at full lane capacity, i.e. ``max_active_slots * bucket /
      block_len`` — sized DOWN is how paged mode oversubscribes HBM).
    - ``kv_quant`` — ``off`` | ``int8``: int8 pool blocks with
      per-(block, head) scales, dequantized in-kernel at decode.
    - ``prefix_cache`` — share resident full-block prompt prefixes
      across requests (LRU index, evicted when the pool runs dry).
    - ``checkpoint_interval`` — generation continuity (PR 20): snapshot
      every active slot's resume state each time it accrues this many
      new tokens (0 = off).  Snapshots are collected at step boundaries
      and spooled by the engine off the hot path.
    - ``resume`` — admit reclaimed records carrying a valid snapshot as
      RESUMES: prefill over prompt + generated-so-far, continue decoding
      at the exact token position (greedy decode makes the continuation
      token-exact — streamed partials are always a prefix of the
      terminal).  Needs a cache model; bare-state models downgrade
      loudly to restart-from-0.
    """

    max_active_slots: int = 8
    max_tokens: int = 32
    eos_id: Optional[int] = None
    start_id: int = 1
    max_prompt_len: int = 64
    bucket_lens: Optional[List[int]] = None
    prefill_buckets: Optional[List[int]] = None
    stream_interval: int = 8
    decode_quantum: int = 4
    paged: bool = False
    block_len: int = 16
    pool_blocks: Optional[int] = None
    kv_quant: str = "off"
    prefix_cache: bool = True
    # generation continuity (PR 20): checkpoint active slots' resume
    # state every `checkpoint_interval` generated tokens (0 = off) and
    # admit reclaimed records with a valid snapshot as resumes
    checkpoint_interval: int = 0
    resume: bool = False

    def __post_init__(self):
        self.max_active_slots = max(1, int(self.max_active_slots))
        self.max_tokens = max(1, int(self.max_tokens))
        self.start_id = int(self.start_id)
        self.max_prompt_len = max(1, int(self.max_prompt_len))
        self.stream_interval = max(0, int(self.stream_interval))
        self.decode_quantum = max(1, int(self.decode_quantum))
        self.paged = bool(self.paged)
        self.prefix_cache = bool(self.prefix_cache)
        self.block_len = _pow2_ceil(self.block_len)
        if self.kv_quant not in ("off", "int8"):
            raise ValueError(
                f"kv_quant must be 'off' or 'int8', got {self.kv_quant!r}")
        if self.pool_blocks is not None:
            self.pool_blocks = max(1, int(self.pool_blocks))
        if self.eos_id is not None:
            self.eos_id = int(self.eos_id)
        if self.bucket_lens is None:
            self.bucket_lens = [
                _pow2_ceil(self.max_prompt_len + self.max_tokens)]
        self.bucket_lens = sorted({_pow2_ceil(b) for b in self.bucket_lens})
        if self.prefill_buckets is None:
            self.prefill_buckets = _pow2_ladder(
                min(8, _pow2_ceil(self.max_prompt_len)),
                self.max_prompt_len)
        self.prefill_buckets = sorted(
            {_pow2_ceil(b) for b in self.prefill_buckets})
        # a user-supplied ladder must still cover every ADMISSIBLE prompt
        # (<= max_prompt_len), or valid requests would have no prefill
        # program to land in
        cap = _pow2_ceil(self.max_prompt_len)
        if self.prefill_buckets[-1] < cap:
            self.prefill_buckets.append(cap)
        self.checkpoint_interval = max(0, int(self.checkpoint_interval))
        self.resume = bool(self.resume)
        if self.resume:
            # a resume re-prefills over prompt + generated_so_far, which
            # can reach max_prompt_len + max_tokens — extend the ladder so
            # the resume prefill is a warmed program, never a steady-state
            # compile (warmup_manifest walks prefill_buckets; the AOT
            # manifest filters pb > lane automatically)
            rcap = _pow2_ceil(self.max_prompt_len + self.max_tokens)
            last = self.prefill_buckets[-1]
            while last < rcap:
                last *= 2
                self.prefill_buckets.append(last)

    @classmethod
    def from_dict(cls, d: Optional[Dict]) -> "GenerationParams":
        if not isinstance(d, dict):
            return cls()
        known = {f for f in cls.__dataclass_fields__}  # noqa: C416
        return cls(**{k: v for k, v in d.items() if k in known})


class GenRequest:
    """One admitted generation request (engine-internal)."""

    __slots__ = ("rid", "prompt", "deadline_ns", "trace_id", "t_read",
                 "max_tokens", "t_submit", "t_admit", "tenant",
                 "resume_tokens", "epoch")

    def __init__(self, rid: str, prompt: np.ndarray,
                 deadline_ns: Optional[int] = None,
                 trace_id: Optional[str] = None,
                 t_read: Optional[float] = None,
                 max_tokens: Optional[int] = None,
                 tenant: Optional[str] = None,
                 resume_tokens: Optional[List[int]] = None,
                 epoch: int = 0):
        self.rid = rid
        self.prompt = prompt            # ORIGINAL prompt, resume or not
        self.deadline_ns = deadline_ns
        self.trace_id = trace_id
        self.t_read = t_read
        self.max_tokens = max_tokens
        self.tenant = tenant
        # generation continuity (PR 20): tokens a dead owner already
        # produced — admission pre-seeds the slot with them and prefills
        # over prompt + resume_tokens; epoch counts ownership handoffs
        self.resume_tokens = resume_tokens
        self.epoch = int(epoch)
        self.t_submit = time.monotonic()
        # popped from the waiting room by the admission that kept it
        self.t_admit: Optional[float] = None


@dataclass
class GenEvent:
    """One scheduler outcome the engine must act on.

    ``kind``: ``first_token`` (TTFT stamp), ``partial`` (stream
    tokens-so-far), ``finish`` (terminal result), ``shed``
    (deadline-exceeded at a step boundary), ``quarantine`` (poisoned
    request isolated), ``resume_failed`` (PR 20: a resume prefix could
    not be replayed — the request restarts from token 0, loudly;
    ``tokens`` carries the wasted prefix, ``error`` the reason)."""

    kind: str
    rid: str
    trace_id: Optional[str] = None
    tokens: Optional[List[int]] = None
    finish_reason: Optional[str] = None
    error: Optional[str] = None
    ttft_s: Optional[float] = None
    t_read: Optional[float] = None
    wall_s: Optional[float] = None
    tenant: Optional[str] = None       # attribution (PR 19)
    # the TTFT chain's stamps (monotonic): ``first_token`` carries t_admit
    # and t_first, the request's FIRST output event (partial or finish)
    # t_first and t_out
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_out: Optional[float] = None


class _Slot:
    __slots__ = ("req", "generated", "t_first", "t_out", "last_stream",
                 "budget", "ckpt_mark")

    def __init__(self, req: GenRequest, budget: int):
        self.req = req
        self.generated: List[int] = []
        self.t_first: Optional[float] = None
        self.t_out: Optional[float] = None     # first partial/finish event
        self.last_stream = 0
        self.budget = budget
        # tokens-generated count at the last checkpoint (PR 20)
        self.ckpt_mark = 0


class _Lane:
    """One capacity bucket: fixed (max_active, bucket) state buffers plus
    the host-side slot map."""

    def __init__(self, bucket: int, max_active: int):
        self.bucket = int(bucket)
        self.max_active = int(max_active)
        self.slots: List[Optional[_Slot]] = [None] * self.max_active
        self.free: deque = deque(range(self.max_active))
        self.state = None                  # device pytree, lazily allocated
        self.tokens = np.zeros((self.max_active,), np.int32)

    @property
    def active(self) -> int:
        return self.max_active - len(self.free)


class _PagedLane(_Lane):
    """Paged-KV lane (PR 18): ``state`` holds the POOL pytree instead of
    per-slot caches, and the per-slot cache geometry lives in host-side
    block tables.  Inactive slots keep their table row zeroed (every
    entry -> the trash block), so their in-program decode writes land
    harmlessly.  ``state_shapes`` is the pool's ``ShapeDtypeStruct`` tree,
    taken once at allocation: programs are lowered from it, never from the
    live (donated) pool."""

    def __init__(self, bucket: int, max_active: int, block_len: int):
        super().__init__(bucket, max_active)
        self.state_shapes = None
        self.state_nbytes = 0              # the pool's bytes on the device
        self.ntab = bucket // block_len
        self.tables = np.zeros((max_active, self.ntab), np.int32)
        self.pos = np.zeros((max_active,), np.int32)
        # per-slot owned block ids (shared-prefix refs + private), for
        # release on free
        self.blocks: List[Optional[List[int]]] = [None] * max_active


class ContinuousBatcher:
    """Token-level decode scheduler over an ``InferenceModel`` whose inner
    layer exposes ``init_decode``/``decode_step`` (see module docstring).

    Thread contract: ``submit`` may be called from any thread (bounded
    waiting deque); ``step``/``warm`` must run on ONE thread (the engine's
    ``serving-generate`` worker)."""

    MAX_WAITING = 1024
    # programs that take a paged lane's pool and return its successor, in
    # the order ``_paged_fns`` returns their jit functions
    _POOL_PROGRAMS = ("pprefill", "pshared", "pdecode")
    # what ``paged=True`` asks of a model: the pool is ITS state, opaque
    # here (``models/textmodels.TransformerLM`` documents the contract)
    _PAGED_CONTRACT = ("init_paged_pools", "prefill_paged",
                       "prefill_shared_paged", "decode_paged",
                       "paged_state_bytes")
    # (optional beside them: ``matmul_operands``, ``paged_counters``)

    def __init__(self, model, gen: GenerationParams):
        inner = getattr(model, "_model", None)
        if inner is None or not hasattr(inner, "init_decode") \
                or not hasattr(inner, "decode_step"):
            raise ValueError(
                "generation needs a model whose topology implements "
                "init_decode/decode_step (models/seq2seq.Seq2seq, "
                "models/textmodels.TransformerLM)")
        self.model = model
        self.inner = inner
        self.gen = gen
        import inspect
        sig = inspect.signature(inner.init_decode)
        # cache models (fixed-length KV caches) take cache_len and their
        # prefill yields first-token logits; bare-state models (LSTM
        # stacks) take neither and start from gen.start_id
        self._cache_model = "cache_len" in sig.parameters
        self._vocab = int(getattr(inner, "vocab_size", 0) or 0)
        model_cap = int(getattr(inner, "max_len", 0) or 0)
        # a cache lane must fit under the model's max_len AND hold at
        # least the smallest prefill bucket (prefill allocates the cache
        # at lane capacity, so cache_len >= prompt bucket must hold)
        usable = [
            b for b in gen.bucket_lens
            if not (self._cache_model
                    and ((model_cap and b > model_cap)
                         or b < gen.prefill_buckets[0]))]
        if not usable:
            raise ValueError(
                f"no usable decode lane: bucket_lens={gen.bucket_lens} "
                f"all exceed the model's max_len={model_cap} or fall "
                f"below the smallest prefill bucket "
                f"{gen.prefill_buckets[0]}")
        if len(usable) < len(gen.bucket_lens):
            logger.warning(
                "generate: dropped %d unusable decode lane(s) from "
                "bucket_lens=%s (model max_len=%s, smallest prefill "
                "bucket %d)", len(gen.bucket_lens) - len(usable),
                gen.bucket_lens, model_cap or "n/a",
                gen.prefill_buckets[0])
        self._pool = None
        self._prefix = None
        self.pool_exhausted = 0
        self.pool_rebuilds = 0       # pools lost to a failed donated call
        self._exhausted_boundary = False
        if gen.paged:
            missing = [m for m in self._PAGED_CONTRACT
                       if not hasattr(inner, m)]
            if missing:
                raise ValueError(
                    "generation.paged=true needs a model with the paged "
                    "contract (models/textmodels.TransformerLM); "
                    f"missing: {missing}")
            bucket = max(usable)
            if gen.block_len > bucket:
                raise ValueError(
                    f"block_len={gen.block_len} > lane capacity {bucket}")
            # ONE paged lane at the largest capacity: block tables make
            # per-request capacity a table-width concern, not a lane
            # concern, so the bucket ladder collapses
            lane = _PagedLane(bucket, gen.max_active_slots, gen.block_len)
            self._lanes = [lane]
            from analytics_zoo_tpu.serving.kvpool import (BlockPool,
                                                          PrefixIndex)
            n_pool = gen.pool_blocks if gen.pool_blocks is not None \
                else gen.max_active_slots * lane.ntab
            self._pool = BlockPool(n_pool, gen.block_len)
            if gen.prefix_cache:
                if not getattr(inner, "paged_prefix_sharing", True):
                    raise ValueError(
                        f"generation.prefix_cache=true, but "
                        f"{type(inner).__name__} cannot prefill behind a "
                        f"shared prefix (it keeps per-slot state that no "
                        f"resident block holds); set prefix_cache: false")
                self._prefix = PrefixIndex(self._pool)
        else:
            self._lanes = [_Lane(b, gen.max_active_slots) for b in usable]
        self._waiting: deque = deque()
        self._waiting_lock = threading.Lock()
        # per-boundary decode accounting (PR 13 tracing): after each
        # step(), (rid, trace_id, tokens_emitted_this_boundary) for every
        # slot that ran a decode step — the engine turns these into the
        # per-boundary decode spans TTFT decomposition needs
        self.last_boundary: List[Tuple] = []
        self.last_admitted = 0       # admissions at the last boundary
        # compiled programs: ("prefill", pb, lane_bucket) |
        # ("decode_step", lane_bucket) | ("insert", lane_bucket)
        self._programs: Dict[tuple, object] = {}
        # per-program execution counts (PR 15 resource accounting):
        # scheduler-thread-only, keyed by the manifest-style program name
        self._exec_counts: Dict[str, int] = {}
        # one record a program MADE (``aot.compile_recorded``, PR 37): when,
        # for whom, the seconds of lowering and compiling, the persistent
        # cache's verdict and the executable's bytes; in the order made,
        # and by key (a key made again after a changed matmul rule keeps
        # its newest).  ``alias_bytes`` there is what a paged program
        # takes over in place of the pool it is handed; over the calls of
        # those programs: pool bytes handed in, and how many were aliased
        self.program_records: List[Dict] = []
        self._record_of: Dict[tuple, Dict] = {}
        self.state_bytes_passed = 0
        self.state_bytes_aliased = 0
        # the weights' operand form (``_params``): the tree and the rule it
        # was made from, the form, how often it was made and the bytes of the
        # copies it holds beside the model's own tree (0: it IS that tree)
        self._operand_lock = threading.Lock()
        self._operand_src, self._operand_form = (None, None), None
        self.operand_builds = 0
        self.operand_bytes = 0
        self.compiles = 0
        self.decode_steps = 0
        self.boundaries = 0          # calls of a decode program
        self.generated_tokens = 0
        self.admitted = 0
        self.finished = 0
        self.quarantined = 0
        self.shed = 0
        # generation continuity (PR 20): resume admissions, loud
        # downgrades to restart-from-0, and checkpoints collected at step
        # boundaries (the engine drains + spools them off the hot path);
        # snapshot_bytes mirrors the spool size for the ResourceLedger
        self.resumed = 0
        self.resume_failed = 0
        self.checkpoints = 0
        self.snapshot_bytes = 0
        self.pending_checkpoints: List[Dict] = []
        # the generate thread's phase clock (PR 25): step()/_admit* switch
        # it, the engine's loop switches it around them; warm() runs on
        # another thread and never touches it
        import jax
        self.clock = PhaseClock(PHASES, annotate=jax.profiler.TraceAnnotation,
                                prefix=PHASE_SPAN_PREFIX)
        # the start-up marks ``stats()`` publishes: the process's, until an
        # engine hands over its own (``ClusterServing``)
        self.startup = get_startup()
        # the TTFT chain (PR 25), cumulative seconds and counts, each added
        # where its interval ends: read -> submit (engine intake), submit ->
        # admit (waiting room), admit -> first token (batch assembly +
        # prefill), first token -> first output event (stream_interval)
        self.intake_s_sum = self.queue_wait_s_sum = 0.0
        self.prefill_s_sum = self.first_out_s_sum = 0.0
        self.intake_n = self.queue_wait_n = 0
        self.prefill_n = self.first_out_n = 0
        # prefill work, useful over attempted: prompt positions asked for
        # vs positions the (batch bucket x prompt bucket) programs computed
        self.prefill_positions_real = 0
        self.prefill_positions_padded = 0
        # COMPILE_STATS listeners: steady-state zero-compile evidence
        from analytics_zoo_tpu.inference import aot
        aot.install_compile_listeners()
        # lane buffers allocated EAGERLY: the warm-up thread and the
        # generate worker both look at lane.state, and lazy allocation
        # would let one overwrite the other's freshly-inserted request
        # state.  (A paged lane's warm-up lowers from state_shapes alone.)
        # (Program compiles stay lock-free — a rare duplicate compile is
        # benign, and serializing them would queue a live request behind
        # the whole warm-up set.)
        for lane in self._lanes:
            self._ensure_lane_state(lane)
        # what the model's programs count on the device (the paged
        # contract's OPTIONAL ``paged_counters(state) -> {name: number}``,
        # e.g. expert load): read on this thread where ``fold`` has just
        # waited for a decode call's tokens, never while a call holds the
        # donated state; ``stats()`` publishes the last reading as
        # ``model.<name>``.  Empty for a model without the method.
        self._model_counters = getattr(inner, "paged_counters", None) \
            if gen.paged else None
        self.model_counters: Dict[str, float] = {} \
            if self._model_counters is None \
            else self._model_counters(self._lanes[0].state)

    # -- program construction (compile-once) ----------------------------------
    def _params(self):
        """The tree every program is lowered from and called with: the
        model's weights in their OPERAND FORM (``matmul_operands``: the
        matmul weights in the type the backend's matmul rounds them to,
        rounded once instead of in every call; the float32 tree itself
        where the backend rounds nothing).  Made once for the tree
        ``model._params`` currently IS under the rule
        (``matmul_operand_dtype``) now in force, again when a load or a
        sharding replaces that tree or the rule changes (a user who turns
        jax to ``highest`` gets float32 operands from the next call on);
        a model without the method is served with its parameters as they
        are."""
        params = self.model._params
        operands = getattr(self.inner, "matmul_operands", None)
        if operands is None:
            return params
        from analytics_zoo_tpu.ops import dispatch
        dtype = dispatch.matmul_operand_dtype()
        # warm-up thread and generate thread both come through here
        with self._operand_lock:
            src, had = self._operand_src
            if src is not params or had is not dtype:
                import jax
                form = operands(params, dtype)
                held = {id(leaf) for leaf in jax.tree.leaves(params)}
                self.operand_bytes = sum(
                    int(leaf.nbytes) for leaf in jax.tree.leaves(form)
                    if id(leaf) not in held)
                if self.operand_builds and had is not dtype:
                    # executables compiled for the other form take
                    # neither these operands nor the new precision
                    self._programs = {
                        k: v for k, v in self._programs.items()
                        if k[0] in ("fns", "pfns")}
                self.operand_builds += 1
                self._operand_src, self._operand_form = (params, dtype), form
            return self._operand_form

    def _jit_key_fns(self, lane_bucket: int):
        import jax
        inner = self.inner

        if self._cache_model:
            def prefill(p, prompt, lengths):
                return inner.init_decode(p, prompt, lengths,
                                         cache_len=lane_bucket)
        else:
            def prefill(p, prompt, lengths):
                return inner.init_decode(p, prompt, lengths)

        K = self.gen.decode_quantum

        def step(p, state, tokens):
            # K decode steps under one lax.scan: one dispatch + one host
            # sync per K tokens.  No in-program EOS logic — the host sees
            # all K tokens per slot and discards everything past a row's
            # EOS/budget; a freed slot's state is fully overwritten by the
            # next insert, so post-finish garbage never leaks.
            def body(carry, _):
                st, tok = carry
                logits, st2 = inner.decode_step(p, st, tok)
                nxt = jax.numpy.argmax(logits, axis=-1).astype("int32")
                return (st2, nxt), nxt

            (st, _), toks = jax.lax.scan(body, (state, tokens), None,
                                         length=K)
            return toks, st            # toks: (K, max_active)

        def insert(state, sub, row, slot):
            # one admitted request: copy `sub` row `row` (an admission
            # batch member) into lane slot `slot`
            return jax.tree.map(lambda L, s: L.at[slot].set(s[row]),
                                state, sub)

        return (jax.jit(prefill), jax.jit(step), jax.jit(insert))

    def _lane_fns(self, lane: _Lane):
        key = ("fns", lane.bucket)
        fns = self._programs.get(key)
        if fns is None:
            fns = self._jit_key_fns(lane.bucket)
            self._programs[key] = fns
        return fns

    def _paged_fns(self):
        """The three paged-mode jit functions (PR 18), thin closures over
        the model's paged contract: ``pprefill`` (prompt forward + block
        commit in ONE program, so raw prompt K/V never leaves the device),
        ``pshared`` (suffix-only prefill over pool-resident prefix blocks
        + commit) and ``pdecode`` (decode_quantum paged decode steps under
        one scan).  ``pools`` is the model's state, opaque here."""
        key = ("pfns",)
        fns = self._programs.get(key)
        if fns is not None:
            return fns
        import jax
        import jax.numpy as jnp
        inner = self.inner
        fmt = dict(block_len=self.gen.block_len, kv_quant=self.gen.kv_quant)
        K = self.gen.decode_quantum

        def pprefill(p, prompt, lengths, pools, dest, slots):
            return inner.prefill_paged(p, pools, prompt, lengths, dest,
                                       slots, **fmt)

        def pshared(p, suffix, slens, prefix_len, ptab, pools, dest,
                    slots):
            return inner.prefill_shared_paged(p, pools, suffix, slens,
                                              prefix_len, ptab, dest,
                                              slots, **fmt)

        def pdecode(p, pools, tables, pos, tokens):
            def body(carry, _):
                pl_, po_, tok = carry
                logits, pl2 = inner.decode_paged(p, pl_, tables, po_, tok,
                                                 **fmt)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (pl2, po_ + 1, nxt), nxt

            (pools2, _, _), toks = jax.lax.scan(
                body, (pools, jnp.asarray(pos, jnp.int32), tokens), None,
                length=K)
            return toks, pools2           # toks: (K, max_active)

        # pdecode donates the pool it replaces: its call site assigns
        # the output over lane.state, so the buffers are the program's to
        # update in place.  The two prefill programs would be as safe to
        # donate into (their call sites replace lane.state too) and are
        # the follow-up: freed of the second pool's 3 GB, the TPU compiler
        # emits a gpt2-large prefill as an executable of 26-43 MB against
        # 2.4-3.2 MB (undonated beside a SMALL pool it does the same), and
        # a deployment's set of those outgrows a capped persistent compile
        # cache, so every start would be a cold one (PERF.md sections 6
        # and 7, PR 29).
        fns = (jax.jit(pprefill), jax.jit(pshared),
               jax.jit(pdecode, donate_argnums=(1,)))
        self._programs[key] = fns
        return fns

    def _compiled(self, key: tuple, lane: _Lane, cause: str = "request"):
        """AOT-compiled executable for one fixed-shape program, compiled
        exactly once; ``warm()`` walks the same path (``cause="warmup"``),
        so a warmed program is the very executable the hot path runs.
        Making one leaves its record (``program_records``); a program the
        generate thread had to make reads ``cause == "request"``."""
        # a changed matmul rule drops the other form's executables HERE,
        # before the look-up, not between it and the call
        self._params()
        exe = self._programs.get(key)
        if exe is None:
            from analytics_zoo_tpu.inference import aot
            fn, args = self._lowering(key, lane)
            exe, record = aot.compile_recorded(
                fn, args, self._program_name(key), cause)
            self._record_of[key] = record
            self.program_records.append(record)
            self._programs[key] = exe
            self.compiles += 1
        return exe

    def _lowering(self, key: tuple, lane: _Lane):
        """Program ``key``'s jit function and the arguments it is lowered
        from.  A paged program lowers from the very tuple its call passes
        (``_paged_args``) over the pool's SHAPES: a live pool may be
        mid-call on the generate thread, donated and deleted."""
        import jax
        kind = key[0]
        if kind in self._POOL_PROGRAMS:
            fn = self._paged_fns()[self._POOL_PROGRAMS.index(kind)]
            return fn, self._paged_args(key, lane, lane.state_shapes)
        prefill, step, insert = self._lane_fns(lane)
        if kind == "prefill":
            _, bb, pb, _ = key
            return prefill, (self._params(), np.zeros((bb, pb), np.int32),
                             np.ones((bb,), np.int32))
        if kind == "decode_step":
            return step, (self._params(), lane.state, lane.tokens)
        # insert: the prefilled sub-state it copies rows from, derived
        # abstractly (its shapes do not depend on the prompt bucket)
        shapes = jax.eval_shape(
            prefill, self._params(),
            jax.ShapeDtypeStruct((key[1], self.gen.prefill_buckets[0]),
                                 np.int32),
            jax.ShapeDtypeStruct((key[1],), np.int32))
        sub = shapes[0] if self._is_pair(shapes) else shapes
        return insert, (lane.state, sub, np.int32(0), np.int32(0))

    def _paged_args(self, key: tuple, lane: "_PagedLane", state,
                    members=()) -> tuple:
        """The argument tuple of paged program ``key``, written once: the
        program is lowered from it (``state`` = the pool's shapes, no
        ``members``: a batch of zeros) and called with it (the live pool,
        the admission group's ``(req, slot, resv)`` members).  A prefill
        batch: right-padded prompts (for ``pshared`` the suffixes, with
        each row's prefix length and prefix block table), their lengths,
        the pool blocks each row's K/V lands in (``dest``; 0 = the trash
        block) and the rows' decode slots (``max_active`` = the sentinel
        that drops a padding row)."""
        if key[0] == "pdecode":
            return (self._params(), state, lane.tables, lane.pos,
                    lane.tokens)
        _, bb, pb, *npb = key
        bl = self.gen.block_len
        padded = np.zeros((bb, pb), np.int32)
        lengths = np.ones((bb,), np.int32)
        dest = np.zeros((bb, (pb + bl - 1) // bl), np.int32)
        slots = np.full((bb,), lane.max_active, np.int32)
        prefix = (np.zeros((bb,), np.int32),
                  np.zeros((bb, npb[0]), np.int32)) if npb else ()
        for j, (req, slot, (ksh, shared_ids, priv, _)) in enumerate(members):
            # on a prefix hit only the suffix is prefilled, into the
            # blocks behind the ksh shared ones
            suffix = self._concat_prompt(req)[ksh * bl:]
            padded[j, :suffix.size] = suffix
            lengths[j] = suffix.size
            if prefix:
                prefix[0][j] = ksh * bl
                prefix[1][j, :ksh] = shared_ids
            nfill = (suffix.size + bl - 1) // bl
            dest[j, :nfill] = priv[:nfill]
            slots[j] = slot
        if members:
            # padding rows replicate row 0's prompt; their dest stays at
            # the trash block and their slot at the drop sentinel, so
            # nothing they compute is ever committed
            for a in (padded, lengths, *prefix):
                a[len(members):] = a[0]
        return (self._params(), padded, lengths, *prefix, state, dest,
                slots)

    @staticmethod
    def _program_name(key: tuple) -> str:
        """Manifest-style label for one compiled scheduler program
        (PR 15 per-program exec accounting)."""
        if key[0] == "prefill":
            return f"prefill:b{key[1]}xp{key[2]}@{key[3]}"
        if key[0] == "insert":
            return f"insert:b{key[1]}@{key[2]}"
        if key[0] == "decode_step":
            return f"decode_step@{key[1]}"
        if key[0] == "pprefill":
            return f"paged_prefill:b{key[1]}xp{key[2]}"
        if key[0] == "pshared":
            return f"paged_shared:b{key[1]}xs{key[2]}xn{key[3]}"
        if key[0] == "pdecode":
            return f"paged_decode@{key[1]}"
        return ":".join(str(k) for k in key)

    def _count_exec(self, key: tuple,
                    lane: Optional["_PagedLane"] = None) -> None:
        """One finished call of program ``key``; ``lane`` where the call
        took that paged lane's pool."""
        # scheduler-thread-only (step/admit run on one thread)
        label = self._program_name(key)
        self._exec_counts[label] = self._exec_counts.get(label, 0) + 1
        if lane is not None:
            # raw on both sides: a share above 100 is a miscount to find
            self.state_bytes_passed += lane.state_nbytes
            self.state_bytes_aliased += self._record_of[key]["alias_bytes"]

    def _commit_state(self, state):
        """Commit a lane state buffer over the serving mesh (PR 6): slot
        axis over ``data`` when it divides, replicated otherwise.
        Single-chip models pass through."""
        mesh = getattr(self.model, "_mesh", None)
        if mesh is None:
            return state
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        dd = int(mesh.shape.get("data", 1))
        A = self.gen.max_active_slots
        shard_rows = dd > 1 and A % dd == 0

        def place(a):
            spec = P("data", *([None] * (a.ndim - 1))) \
                if (shard_rows and a.ndim >= 1) else P()
            return jax.device_put(a, NamedSharding(mesh, spec))

        return jax.tree.map(place, state)

    def _ensure_lane_state(self, lane: _Lane):
        if lane.state is not None:
            return
        import jax
        if isinstance(lane, _PagedLane):
            lane.state = self._zeroed_pool(lane)
            lane.state_shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                lane.state)
            # as the device lays the leaves out (a TPU pads int8 pools'
            # scale planes and staging buffers to its tiles), which is
            # what memory_analysis() counts an aliased leaf as
            lane.state_nbytes = sum(
                leaf.on_device_size_in_bytes()
                for leaf in jax.tree.leaves(lane.state))
            return
        pb = self.gen.prefill_buckets[0]
        prefill, _, _ = self._lane_fns(lane)
        A = lane.max_active
        shapes = jax.eval_shape(
            prefill, self._params(),
            jax.ShapeDtypeStruct((A, pb), np.int32),
            jax.ShapeDtypeStruct((A,), np.int32))
        state_shapes = shapes[0] if self._is_pair(shapes) else shapes
        lane.state = self._commit_state(jax.tree.map(
            lambda sd: np.zeros(sd.shape, sd.dtype), state_shapes))
        lane.state = jax.device_put(lane.state) \
            if getattr(self.model, "_mesh", None) is None else lane.state

    def _zeroed_pool(self, lane: "_PagedLane"):
        """Pool pytree on the device: +1 block for the reserved trash row;
        placed whole (no slot axis to shard — the pool IS the point)."""
        import jax
        return jax.device_put(self.inner.init_paged_pools(
            self._pool.n_blocks + 1, self.gen.block_len, lane.max_active,
            self.gen.kv_quant))

    @staticmethod
    def _pool_lost(lane: "_PagedLane") -> bool:
        """After an exception out of a paged program: did the call take
        the pool with it?  True when the runtime already consumed the
        donated buffers (a leaf is deleted) or the program that was to
        produce the new ones failed on the device (waiting on a leaf
        raises).  False for an error raised before execution (arguments,
        shapes): the pool is untouched."""
        import jax
        leaves = jax.tree.leaves(lane.state)
        if any(leaf.is_deleted() for leaf in leaves):
            return True
        try:
            jax.block_until_ready(leaves)
        except Exception:  # noqa: BLE001 — the failed program's outputs
            return True
        return False

    def _rebuild_pool(self, lane: "_PagedLane", err: Exception,
                      events: List[GenEvent]) -> None:
        """The lane's KV is gone (``_pool_lost``): nothing may run on it
        again.  Every active request ends with a quarantine event that
        carries the error, blocks and prefix index are released, and the
        lane starts over from a zeroed pool."""
        error = f"{type(err).__name__}: {err}"
        ended = 0
        for slot, info in enumerate(lane.slots):
            if info is None:
                continue
            ended += 1
            self._quarantine(
                info.req, f"KV pool lost in a failed call: {error}", events)
            self._free(lane, slot)
        if self._prefix is not None:
            self._prefix.clear()
        lane.state = self._zeroed_pool(lane)
        self.pool_rebuilds += 1
        logger.error(
            "generate: a paged program failed after taking the KV pool "
            "(%s); %d active request(s) ended, pool rebuilt (%d so far)",
            error, ended, self.pool_rebuilds)

    @staticmethod
    def _is_pair(res) -> bool:
        """(state, logits) vs bare state: cache models return a 2-tuple
        whose second element is a rank-2 logits array."""
        return (isinstance(res, tuple) and len(res) == 2
                and hasattr(res[1], "shape")
                and getattr(res[1], "ndim", 0) == 2)

    # -- admission ------------------------------------------------------------
    def _quarantine(self, req: GenRequest, error: str,
                    events: List[GenEvent]) -> None:
        """End ``req`` alone, with the error its client will read."""
        self.quarantined += 1
        events.append(GenEvent(
            "quarantine", req.rid, trace_id=req.trace_id, error=error,
            t_read=req.t_read, tenant=req.tenant))

    def submit(self, req: GenRequest) -> bool:
        """Queue one request for the next step boundary.  False = waiting
        room full (caller should leave the record staged / backpressure)."""
        with self._waiting_lock:
            if len(self._waiting) >= self.MAX_WAITING:
                return False
            self._waiting.append(req)
            if req.t_read is not None:
                self.intake_s_sum += req.t_submit - req.t_read
                self.intake_n += 1
            return True

    @property
    def waiting(self) -> int:
        with self._waiting_lock:
            return len(self._waiting)

    @property
    def active(self) -> int:
        return sum(lane.active for lane in self._lanes)

    @property
    def slots_total(self) -> int:
        return sum(lane.max_active for lane in self._lanes)

    def _req_budget(self, req: GenRequest) -> int:
        """Per-request token budget: the deployment cap, lowerable (never
        raisable) by the record's own max_tokens.  The ONE clamp both
        lane selection and the slot budget use — they must agree, or a
        request could land in a lane too small for its budget."""
        budget = self.gen.max_tokens
        if req.max_tokens is not None:
            budget = max(1, min(int(req.max_tokens), budget))
        return budget

    def _budget_for(self, req: GenRequest, lane: _Lane) -> int:
        budget = self._req_budget(req)
        if self._cache_model:
            budget = min(budget, lane.bucket - len(req.prompt))
        return max(1, budget)

    def _pick_lane(self, req: GenRequest) -> Optional[_Lane]:
        """Smallest lane whose capacity holds prompt + budget AND the
        prompt's padded prefill bucket (prefill allocates the cache at
        the lane capacity, so ``cache_len >= prefill bucket`` must hold);
        bare-state models (no length axis) use the first lane.  A resume
        (PR 20) prefills over prompt + resume prefix, so its prefill
        bucket is computed from the CONCAT length; total cache occupancy
        is still prompt + budget (the prefix counts against the budget)."""
        if not self._cache_model:
            return self._lanes[0]
        want = len(req.prompt) + self._req_budget(req)
        pb = self._prefill_bucket(len(req.prompt)
                                  + len(req.resume_tokens or ()))
        if pb is not None:
            want = max(want, pb)
        for lane in self._lanes:
            if lane.bucket >= want:
                return lane
        return None

    # -- resume admission (PR 20) ---------------------------------------------
    def _concat_prompt(self, req: GenRequest) -> np.ndarray:
        """Prefill input: the original prompt, plus — for a resume — the
        tokens the dead owner already produced (replaying them through
        prefill rebuilds the exact cache a continuous decode would hold,
        and greedy decode over it continues token-exactly)."""
        p = np.asarray(req.prompt).astype(np.int32).reshape(-1)
        if not req.resume_tokens:
            return p
        return np.concatenate([p, np.asarray(req.resume_tokens,
                                             np.int32)])

    def _downgrade_resume(self, req: GenRequest, reason: str,
                          events: List[GenEvent]) -> None:
        """Fall back LOUDLY to restart-from-0: the wasted prefix rides
        the event so the engine can meter it."""
        toks = [int(t) for t in req.resume_tokens or ()
                if isinstance(t, (int, float, np.integer))]
        req.resume_tokens = None
        self.resume_failed += 1
        events.append(GenEvent(
            "resume_failed", req.rid, trace_id=req.trace_id,
            tokens=toks, error=reason, t_read=req.t_read,
            tenant=req.tenant))

    def _take_resume(self, req: GenRequest,
                     events: List[GenEvent]) -> None:
        """Normalize a reclaimed request's resume prefix, downgrading to
        restart-from-0 when it cannot be replayed: bare-state models
        rebuild no cache at prefill (continuing would NOT be a prefix of
        an uninterrupted run), and a malformed or out-of-vocab prefix
        would poison the decode state."""
        rt = req.resume_tokens
        if not rt:
            req.resume_tokens = None
            return
        try:
            toks = [int(t) for t in rt]
        except (TypeError, ValueError):
            self._downgrade_resume(req, "malformed resume prefix", events)
            return
        if not self._cache_model:
            self._downgrade_resume(
                req, "bare-state model cannot replay decode state",
                events)
            return
        if self._vocab and toks and (min(toks) < 0
                                     or max(toks) >= self._vocab):
            self._downgrade_resume(
                req, "resume token id out of vocab range", events)
            return
        cap = self._req_budget(req) - 1
        if cap < 1:
            self._downgrade_resume(req, "token budget already consumed",
                                   events)
            return
        # a prefix at/over budget should have finished at the old owner;
        # keep budget-1 so the resumed slot still decodes >= 1 token
        req.resume_tokens = toks[:cap]

    def _seed_resume(self, info: _Slot) -> None:
        """Pre-seed a just-admitted slot with its resume prefix: the
        terminal token list stays the full generation (partials remain a
        prefix of it), while `last_stream`/`ckpt_mark` start past the
        prefix so streaming cadence and checkpoint cadence resume where
        the dead owner left off.  `step()`'s boundary accounting reports
        only post-admission deltas, so the engine meters delta tokens
        only — no double-billing across the resume epoch."""
        rt = info.req.resume_tokens
        if not rt:
            return
        info.generated = [int(t) for t in rt]
        info.last_stream = len(info.generated)
        info.ckpt_mark = len(info.generated)
        self.resumed += 1

    def _validate(self, req: GenRequest) -> Optional[str]:
        p = np.asarray(req.prompt)
        if p.ndim != 1 or p.size == 0:
            return f"prompt must be a non-empty 1-D token sequence, got " \
                   f"shape {p.shape}"
        if p.size > self.gen.max_prompt_len:
            return f"prompt length {p.size} > max_prompt_len " \
                   f"{self.gen.max_prompt_len}"
        if not np.all(np.isfinite(p)):
            return "prompt contains non-finite token ids"
        ids = p.astype(np.int64)
        if self._vocab and (ids.min() < 0 or ids.max() >= self._vocab):
            return f"token id out of range [0, {self._vocab})"
        return None

    def _prefill_bucket(self, n: int) -> Optional[int]:
        for b in self.gen.prefill_buckets:
            if b >= n:
                return b
        return None

    def _batch_bucket(self, n: int) -> int:
        """Admission-batch bucket: smallest pow-2 >= n, capped at the
        slot-count bucket (the grab loop never claims more than a lane's
        slots anyway)."""
        return min(_pow2_ceil(n), _pow2_ceil(self.gen.max_active_slots))

    def _admit_batch(self, lane: _Lane, pb: int, members, events) -> int:
        """Prefill + insert a same-(lane, prompt-bucket) admission group
        in ONE device call.  ``members``: (req, slot) pairs, slots already
        claimed.  B=1 prefill costs ~the same wall as B=8 (call overhead
        dominates at serving widths), so batching admissions is what keeps
        a churning request mix from spending its steps on prefill calls.
        Padding rows replicate row 0's prompt (any valid prompt works —
        their states are computed and discarded, never inserted).

        A failing batch falls back to singleton admission so a poisoned
        request that slipped past validation quarantines ALONE."""
        n = len(members)
        bb = self._batch_bucket(n)
        padded = np.zeros((bb, pb), np.int32)
        lengths = np.ones((bb,), np.int32)
        for j, (req, _) in enumerate(members):
            prompt = self._concat_prompt(req)
            padded[j, :prompt.size] = prompt
            lengths[j] = prompt.size
        for j in range(n, bb):
            padded[j] = padded[0]
            lengths[j] = lengths[0]
        self.prefill_positions_real += int(lengths[:n].sum())
        self.prefill_positions_padded += bb * pb
        try:
            self._ensure_lane_state(lane)
            exe = self._compiled(("prefill", bb, pb, lane.bucket), lane)
            res = exe(self._params(), padded, lengths)
            self._count_exec(("prefill", bb, pb, lane.bucket))
            if self._is_pair(res):
                sub, logits0 = res
                with self.clock.phase("prefill_wait"):
                    logits0 = np.asarray(logits0)
                # host-side argmax (matches the paged path): an eager
                # jnp.argmax would XLA-compile once per batch bucket —
                # a steady-state compile the admission path must not pay
                toks0 = logits0.argmax(axis=-1)
            else:
                sub, toks0 = res, None
            ins = self._compiled(("insert", bb, lane.bucket), lane)
        except Exception as e:  # noqa: BLE001 — batch-level failure
            if n == 1:
                req, slot = members[0]
                self._quarantine(req, f"{type(e).__name__}: {e}", events)
                lane.free.append(slot)
                return 0
            # isolate the poison: singleton admissions, per-slot blast
            # radius — neighbours' state buffers were never touched
            return sum(self._admit_batch(lane, pb, [mem], events)
                       for mem in members)
        admitted = 0
        for j, (req, slot) in enumerate(members):
            try:
                lane.state = ins(lane.state, sub, np.int32(j),
                                 np.int32(slot))
                self._count_exec(("insert", bb, lane.bucket))
            except Exception as e:  # noqa: BLE001 — per-row insert failure
                self._quarantine(req, f"{type(e).__name__}: {e}", events)
                lane.free.append(slot)
                continue
            info = _Slot(req, budget=self._budget_for(req, lane))
            self._seed_resume(info)
            lane.slots[slot] = info
            self.admitted += 1
            admitted += 1
            if toks0 is not None:
                # cache models emit their first token AT prefill: TTFT
                # stops here, and the token feeds the first decode step
                self._first_token(info, time.monotonic(), events)
                lane.tokens[slot] = int(toks0[j])
                self._account_token(lane, slot, info, int(toks0[j]),
                                    events)
            else:
                lane.tokens[slot] = self.gen.start_id
        return admitted

    # -- paged admission (PR 18) ----------------------------------------------
    def _reserve(self, lane: "_PagedLane", req: GenRequest):
        """Claim pool blocks for one request: the longest registered
        prompt prefix rides shared (referenced) pages, the rest allocates
        private blocks — evicting LRU prefix-cache entries if the pool
        runs dry.  Returns ``(k_shared, shared_ids, private_ids, plen)``
        or None (pool exhausted: the caller requeues and a typed
        ``kv_pool_exhausted`` flight-recorder event explains the stall)."""
        prompt = self._concat_prompt(req)
        plen = int(prompt.size)
        bl = self.gen.block_len
        # a resume's prefix tokens count against the budget, so blocks
        # for (concat - prefix) + budget == original prompt + budget
        need = (plen - len(req.resume_tokens or ())
                + self._budget_for(req, lane) + bl - 1) // bl
        need = min(need, lane.ntab)
        ksh, shared = 0, []
        if self._prefix is not None:
            # cap leaves >= 1 suffix token: first-token logits need at
            # least one position to actually prefill
            ksh, shared = self._prefix.lookup(
                prompt, max_blocks=(plen - 1) // bl)
        priv = self._pool.alloc(need - ksh)
        if priv is None and self._prefix is not None:
            self._prefix.evict_for(need - ksh)
            priv = self._pool.alloc(need - ksh)
        if priv is None:
            if shared:
                self._pool.release(shared)
            if not self._exhausted_boundary:
                self._exhausted_boundary = True
                self.pool_exhausted += 1
                from analytics_zoo_tpu.common.observability import \
                    get_recorder
                get_recorder().record(
                    "kv_pool_exhausted", rid=req.rid,
                    need_blocks=int(need - ksh),
                    free_blocks=int(self._pool.free_blocks),
                    active_slots=int(self.active),
                    waiting=int(self.waiting))
            return None
        return ksh, shared, priv, plen

    def _release_resv(self, resv) -> None:
        ksh, shared, priv, _ = resv
        if shared:
            self._pool.release(shared)
        if priv:
            self._pool.release(priv)

    def _admit_paged(self, grabbed, events: List[GenEvent]) -> int:
        """Paged admission of what ``_grab`` claimed (slots AND pool
        blocks): grouped into prefix-MISS batches (full prefill, one
        program per (batch, prompt bucket)) and prefix-HIT batches
        (suffix-only prefill, one program per (batch, suffix bucket,
        prefix-table bucket))."""
        lane: _PagedLane = self._lanes[0]
        bl = self.gen.block_len
        miss: Dict[int, list] = {}
        hit: Dict[tuple, list] = {}
        for req, _, slot, resv in grabbed:
            ksh, _, _, plen = resv
            pb = self._prefill_bucket(plen - ksh * bl)
            if pb is None:               # defensive, as in _admit
                self._release_resv(resv)
                self._quarantine(
                    req, f"ValueError: no prefill bucket holds prompt "
                         f"length {plen} (buckets "
                         f"{self.gen.prefill_buckets})", events)
                lane.free.append(slot)
                continue
            if ksh:
                hit.setdefault((pb, _pow2_ceil(ksh)), []).append(
                    (req, slot, resv))
            else:
                miss.setdefault(pb, []).append((req, slot, resv))
        groups = [(pb, None, members) for pb, members in miss.items()] \
            + [(sb, npb, members) for (sb, npb), members in hit.items()]
        admitted, rebuilds = 0, self.pool_rebuilds
        for i, (pb, npb, members) in enumerate(groups):
            if self.pool_rebuilds != rebuilds:
                # an earlier group's call lost the pool
                self._requeue_reserved(
                    lane, [m for _, _, ms in groups[i:] for m in ms])
                break
            admitted += self._admit_paged_batch(lane, pb, members, events,
                                                shared=npb)
        return admitted

    def _requeue_reserved(self, lane: "_PagedLane", members) -> None:
        """The pool was rebuilt under admissions that had already
        reserved: what they reserved (shared prefix pages above all)
        names contents that are gone.  They give slot and blocks back and
        return to the head of the waiting room, in order, to reserve
        again at the next boundary (``_take_resume`` is idempotent)."""
        for _, slot, resv in members:
            self._release_resv(resv)
            lane.free.append(slot)
        with self._waiting_lock:
            self._waiting.extendleft(req for req, _, _ in reversed(members))

    def _admit_paged_batch(self, lane: "_PagedLane", pb: int, members,
                           events, shared: Optional[int] = None) -> int:
        """Prefill + commit one same-bucket paged admission group in ONE
        device call.  ``shared`` = prefix-table bucket for prefix-HIT
        groups (None = full prefill).  Mirrors ``_admit_batch``'s
        singleton fallback so a poisoned request quarantines alone — as
        long as the failed call left the pool alive (an error raised
        before execution); one that took the pool with it ends the
        group and the lane's active requests (``_rebuild_pool``)."""
        bl = self.gen.block_len
        n = len(members)
        bb = self._batch_bucket(n)
        key = ("pprefill", bb, pb) if shared is None \
            else ("pshared", bb, pb, shared)
        args = self._paged_args(key, lane, lane.state, members)
        self.prefill_positions_real += int(args[2][:n].sum())   # lengths
        self.prefill_positions_padded += bb * pb
        try:
            lane.state, logits0 = self._compiled(key, lane)(*args)
            self._count_exec(key, lane)
            with self.clock.phase("prefill_wait"):
                logits0 = np.asarray(logits0)
            toks0 = logits0.argmax(axis=-1)
        except Exception as e:  # noqa: BLE001 — batch-level failure
            # a call that took the pool with it (its outputs replaced
            # lane.state, then failed on the device) allows no retry, not
            # of these members and not on this pool
            lost = self._pool_lost(lane)
            if lost or n == 1:
                for req, slot, resv in members:
                    self._release_resv(resv)
                    self._quarantine(req, f"{type(e).__name__}: {e}",
                                     events)
                    lane.free.append(slot)
                if lost:
                    self._rebuild_pool(lane, e, events)
                return 0
            done, rebuilds = 0, self.pool_rebuilds
            for i, m in enumerate(members):
                if self.pool_rebuilds != rebuilds:
                    # a retry lost the pool after all
                    self._requeue_reserved(lane, members[i:])
                    break
                done += self._admit_paged_batch(lane, pb, [m], events,
                                                shared=shared)
            return done
        admitted = 0
        for j, (req, slot, resv) in enumerate(members):
            ksh, shared_ids, priv, plen = resv
            table = list(shared_ids) + list(priv)
            lane.tables[slot, :] = 0
            lane.tables[slot, :len(table)] = table
            lane.pos[slot] = plen
            lane.blocks[slot] = table
            info = _Slot(req, budget=self._budget_for(req, lane))
            self._seed_resume(info)
            lane.slots[slot] = info
            self.admitted += 1
            admitted += 1
            if self._prefix is not None and ksh == 0:
                # park the prompt's FULL blocks for future sharers (the
                # partial tail block keeps being written by decode, so
                # it can never be shared); a resume registers the CONCAT
                # prefix — that is what its resident pages actually hold
                full = plen // bl
                if full:
                    prompt = self._concat_prompt(req)
                    self._prefix.register(prompt[:full * bl],
                                          table[:full])
            self._first_token(info, time.monotonic(), events)
            lane.tokens[slot] = int(toks0[j])
            self._account_token(lane, slot, info, int(toks0[j]), events)
        return admitted

    def _grab(self, events: List[GenEvent]) -> List[tuple]:
        """The admission grab loop, paged or not: pop waiting requests in
        order; each is stamped, shed if expired, validated, its resume
        prefix normalised, given its lane (or quarantined) and a free slot
        there — on a paged lane its pool blocks too (``_reserve``).  Stops
        at the first head-of-line request whose lane is full or whose
        blocks the pool cannot give: it returns to the head of the waiting
        room (FIFO; retried next boundary).  Returns ``(req, lane, slot,
        resv)`` tuples, ``resv`` the paged reservation."""
        grabbed: List[tuple] = []
        while True:
            with self._waiting_lock:
                req = self._waiting.popleft() if self._waiting else None
            if req is None:
                break
            req.t_admit = time.monotonic()   # a requeue stamps it again
            if self._expired(req.deadline_ns):
                self.shed += 1
                events.append(GenEvent(
                    "shed", req.rid, trace_id=req.trace_id,
                    t_read=req.t_read, tenant=req.tenant))
                continue
            err = self._validate(req)
            if err is not None:
                self._quarantine(req, f"ValueError: {err}", events)
                continue
            if req.resume_tokens:
                self._take_resume(req, events)
            lane = self._pick_lane(req)
            if lane is None and req.resume_tokens:
                # the concat prefix pushed the prefill bucket past every
                # lane: a VALID request must not quarantine — restart it
                self._downgrade_resume(
                    req, "resume prefix exceeds lane capacity", events)
                lane = self._pick_lane(req)
            if lane is None:
                self._quarantine(
                    req, "ValueError: no decode lane holds prompt + "
                         f"max_tokens (buckets {self.gen.bucket_lens})",
                    events)
                continue
            resv = None
            if lane.free:
                resv = () if self._pool is None \
                    else self._reserve(lane, req)
            if resv is None:
                # every slot of the right lane busy (FIFO per lane is
                # close enough across lanes at this queue depth), or the
                # pool exhausted: the request stays at the head
                with self._waiting_lock:
                    self._waiting.appendleft(req)
                break
            grabbed.append((req, lane, lane.free.popleft(), resv))
        return grabbed

    def _admit(self, events: List[GenEvent]) -> int:
        """Claim free slots for waiting requests (``_grab``) and admit
        them in batched prefill groups."""
        grabbed = self._grab(events)
        if not grabbed:
            return 0
        if self._pool is not None:
            return self._admit_paged(grabbed, events)
        groups: Dict[tuple, list] = {}
        for req, lane, slot, _ in grabbed:
            prompt_len = int(np.asarray(req.prompt).reshape(-1).size) \
                + len(req.resume_tokens or ())
            pb = self._prefill_bucket(prompt_len)
            if pb is None and req.resume_tokens:
                self._downgrade_resume(
                    req, "no prefill bucket holds resume prefix", events)
                prompt_len = int(np.asarray(req.prompt).reshape(-1).size)
                pb = self._prefill_bucket(prompt_len)
            if pb is None:
                # defensive: __post_init__ extends the ladder to cover
                # max_prompt_len, so this is unreachable from config —
                # but an uncovered prompt must quarantine, not crash the
                # worker with its slot claimed
                self._quarantine(
                    req, f"ValueError: no prefill bucket holds prompt "
                         f"length {prompt_len} (buckets "
                         f"{self.gen.prefill_buckets})", events)
                lane.free.append(slot)
                continue
            groups.setdefault((lane.bucket, pb), (lane, pb, []))[2] \
                .append((req, slot))
        return sum(self._admit_batch(lane, pb, members, events)
                   for lane, pb, members in groups.values())

    # -- step boundary --------------------------------------------------------
    @staticmethod
    def _expired(deadline_ns) -> bool:
        if deadline_ns is None:
            return False
        try:
            return time.time_ns() > int(deadline_ns)
        except (TypeError, ValueError, OverflowError):
            return False      # gateway/engine validated upstream

    def _free(self, lane: _Lane, slot: int) -> None:
        if isinstance(lane, _PagedLane):
            if lane.blocks[slot]:
                self._pool.release(lane.blocks[slot])
                lane.blocks[slot] = None
            # zero the table row: the freed slot's in-program writes
            # land in the trash block until the next admission
            lane.tables[slot, :] = 0
            lane.pos[slot] = 0
        lane.slots[slot] = None
        lane.free.append(slot)

    def _first_token(self, info: _Slot, now: float,
                     events: List[GenEvent]) -> None:
        """Stamp a request's first token: the ``first_token`` event (TTFT
        = submit -> now) and the two links of the chain that end here,
        which sum to that TTFT exactly."""
        req = info.req
        info.t_first = now
        self.queue_wait_s_sum += req.t_admit - req.t_submit
        self.queue_wait_n += 1
        self.prefill_s_sum += now - req.t_admit
        self.prefill_n += 1
        events.append(GenEvent(
            "first_token", req.rid, trace_id=req.trace_id,
            ttft_s=now - req.t_submit, t_read=req.t_read, tenant=req.tenant,
            t_admit=req.t_admit, t_first=now))

    def _first_output(self, info: _Slot, now: float, ev: GenEvent) -> None:
        """``ev`` is the first event of this request a client can see
        (partial or finish): close the chain's last link on it."""
        info.t_out = now
        if info.t_first is not None:
            self.first_out_s_sum += now - info.t_first
            self.first_out_n += 1
            ev.t_first, ev.t_out = info.t_first, now

    def _finish(self, lane: _Lane, slot: int, info: _Slot, reason: str,
                events: List[GenEvent]) -> None:
        self.finished += 1
        now = time.monotonic()
        ev = GenEvent(
            "finish", info.req.rid, trace_id=info.req.trace_id,
            tokens=list(info.generated), finish_reason=reason,
            ttft_s=(info.t_first - info.req.t_submit
                    if info.t_first is not None else None),
            t_read=info.req.t_read, wall_s=now - info.req.t_submit,
            tenant=info.req.tenant)
        if info.t_out is None:
            self._first_output(info, now, ev)
        events.append(ev)
        self._free(lane, slot)

    def _account_token(self, lane: _Lane, slot: int, info: _Slot,
                       tok: int, events: List[GenEvent]) -> None:
        """Fold one emitted token into the slot: EOS / budget finish the
        request immediately (slot freed THIS boundary), stream_interval
        flushes partials."""
        eos = self.gen.eos_id
        if eos is not None and tok == eos:
            self._finish(lane, slot, info, "eos", events)
            return
        info.generated.append(int(tok))
        self.generated_tokens += 1
        if len(info.generated) >= info.budget:
            self._finish(lane, slot, info, "length", events)
            return
        si = self.gen.stream_interval
        if si and len(info.generated) - info.last_stream >= si:
            info.last_stream = len(info.generated)
            ev = GenEvent(
                "partial", info.req.rid, trace_id=info.req.trace_id,
                tokens=list(info.generated), t_read=info.req.t_read,
                tenant=info.req.tenant)
            if info.t_out is None:
                self._first_output(info, time.monotonic(), ev)
            events.append(ev)

    def _shed_active(self, events: List[GenEvent]) -> None:
        for lane in self._lanes:
            for slot, info in enumerate(lane.slots):
                if info is None or not self._expired(info.req.deadline_ns):
                    continue
                self.shed += 1
                events.append(GenEvent(
                    "shed", info.req.rid, trace_id=info.req.trace_id,
                    tokens=list(info.generated), t_read=info.req.t_read,
                    tenant=info.req.tenant))
                self._free(lane, slot)

    def step(self) -> List[GenEvent]:
        """One decode-step boundary: shed expired, admit into free slots,
        run one token step per non-empty lane, fold the emitted tokens.
        Returns the events the engine must act on; an idle scheduler
        returns [] without touching the device.  Leaves the clock in the
        last phase it ran (``fold`` after a decode, else ``admit``): the
        caller switches it on."""
        events: List[GenEvent] = []
        clock = self.clock
        clock.to("shed")
        self.last_boundary = []
        self._exhausted_boundary = False
        self._shed_active(events)
        clock.to("admit")
        self.last_admitted = self._admit(events)
        for lane in self._lanes:
            if lane.active == 0:
                continue
            clock.to("dispatch")
            if isinstance(lane, _PagedLane):
                key = ("pdecode", lane.bucket)
                exe = self._compiled(key, lane)
                try:
                    block, lane.state = exe(
                        *self._paged_args(key, lane, lane.state))
                    self._count_exec(key, lane)
                    clock.to("decode_wait")
                    block = np.asarray(block)
                except Exception as e:  # noqa: BLE001 — see _pool_lost
                    if not self._pool_lost(lane):
                        raise
                    # the lane's requests end here, with events the engine
                    # can act on; the loop goes on from a zeroed pool
                    self._rebuild_pool(lane, e, events)
                    continue
                clock.to("fold")
                # host cursors advance with the in-scan carry; idle rows
                # clamp at lane capacity (their writes target the trash
                # block regardless).  MUST run before the token fold —
                # _free zeroes a finishing row's cursor.
                lane.pos = np.minimum(
                    lane.pos + np.int32(block.shape[0]),
                    np.int32(lane.bucket)).astype(np.int32)
                if self._model_counters is not None:
                    self.model_counters = self._model_counters(lane.state)
            else:
                key = ("decode_step", lane.bucket)
                block, lane.state = self._compiled(key, lane)(
                    self._params(), lane.state, lane.tokens)
                self._count_exec(key)
                clock.to("decode_wait")
                block = np.asarray(block)      # (decode_quantum, A)
                clock.to("fold")
            self.boundaries += 1
            self.decode_steps += int(block.shape[0])   # token-level steps
            now = time.monotonic()
            for slot, info in enumerate(lane.slots):
                if info is None:
                    continue
                if info.t_first is None:
                    self._first_token(info, now, events)
                n0 = len(info.generated)
                for k in range(block.shape[0]):
                    self._account_token(lane, slot, info,
                                        int(block[k, slot]), events)
                    if lane.slots[slot] is not info:
                        break      # finished mid-quantum: discard the rest
                # boundary accounting for the per-boundary decode spans
                # and per-tenant token charging (valid whether the
                # request finished this boundary or not — `info` outlives
                # the slot free)
                self.last_boundary.append(
                    (info.req.rid, info.req.trace_id,
                     len(info.generated) - n0, info.req.tenant))
            # copy: the device block is read-only, and the next boundary's
            # admission writes freshly-claimed slots into this row
            lane.tokens = np.array(block[-1])
        if self.gen.checkpoint_interval > 0 and self._cache_model:
            self._collect_checkpoints()
        return events

    def _collect_checkpoints(self) -> None:
        """Queue resume-state snapshots for slots that crossed the
        checkpoint interval since their last mark.  Host-side list work
        only — the engine drains `pending_checkpoints` and spools them
        OFF this thread, so the decode hot path never waits on disk.
        Bare-state models are skipped entirely: their decode state cannot
        be rebuilt by prefill, so a snapshot could never be resumed."""
        interval = self.gen.checkpoint_interval
        now = time.monotonic()
        for lane in self._lanes:
            for info in lane.slots:
                if info is None:
                    continue
                n = len(info.generated)
                if n - info.ckpt_mark < interval:
                    continue
                req = info.req
                prompt = np.asarray(req.prompt).reshape(-1)
                self.pending_checkpoints.append({
                    "rid": req.rid,
                    "epoch": req.epoch,
                    "prompt": [int(t) for t in prompt],
                    "tokens": list(info.generated),
                    "n": n,
                    "tenant": req.tenant,
                    "trace_id": req.trace_id,
                    "deadline_ns": req.deadline_ns,
                    "max_tokens": req.max_tokens,
                    # greedy argmax decode: the "RNG stream" is the
                    # degenerate deterministic one — recorded so a future
                    # sampling decode can refuse to resume across a
                    # sampler change instead of silently diverging
                    "sampler": "greedy",
                    "ts": now,
                })
                info.ckpt_mark = n
                self.checkpoints += 1

    def drain_checkpoints(self) -> List[Dict]:
        """Hand the queued snapshots to the engine (scheduler thread
        only, like `step`)."""
        out, self.pending_checkpoints = self.pending_checkpoints, []
        return out

    @property
    def idle(self) -> bool:
        return self.active == 0 and self.waiting == 0

    # -- warm-up (PR 11 integration) ------------------------------------------
    def warmup_manifest(self):
        """The (prefill-bucket x decode-step) program set for this
        deployment — delegated to ``aot.generation_manifest`` so the
        serving warm-up and ``manager warmup`` derive the same set."""
        from analytics_zoo_tpu.inference import aot
        prefix_blocks: Sequence[int] = ()
        if self._prefix is not None:
            max_sh = (self.gen.max_prompt_len - 1) // self.gen.block_len
            if max_sh >= 1:
                prefix_blocks = _pow2_ladder(1, max_sh)
        return aot.generation_manifest(
            self.gen.prefill_buckets,
            [lane.bucket for lane in self._lanes],
            prefill_batches=_pow2_ladder(1, self.gen.max_active_slots),
            cache_model=self._cache_model,
            paged=self._pool is not None,
            prefix_blocks=prefix_blocks)

    def warm(self, manifest=None, progress: Optional[Callable] = None,
             stop: Optional[Callable[[], bool]] = None) -> Dict:
        """Compile every scheduler program ahead of traffic.  Same stats
        document shape as ``aot.warm_up`` so the engine's warm-up thread
        and ``/readyz`` progress machinery drive either."""
        from analytics_zoo_tpu.inference import aot
        if manifest is None:
            manifest = self.warmup_manifest()
        lanes = {lane.bucket: lane for lane in self._lanes}
        return aot.warm_pass(
            manifest, lambda entry: self._warm_entry(entry, lanes),
            progress=progress, stop=stop, who="generate")

    def _warm_entry(self, entry, lanes: Dict[int, "_Lane"]) -> bool:
        """Compile one manifest entry's program; False = it was there."""
        lane = lanes.get(entry.lane_bucket)
        if lane is None:
            raise ValueError(f"no lane with bucket {entry.lane_bucket}")
        self._ensure_lane_state(lane)
        bb, pb = int(entry.prefill_batch or 1), entry.prefill_bucket
        key = {"paged_decode": ("pdecode", lane.bucket),
               "paged_prefill": ("pprefill", bb, pb),
               "paged_shared": ("pshared", bb, pb,
                                int(entry.prefix_blocks or 1)),
               "prefill": ("prefill", bb, pb, lane.bucket),
               "decode_step": ("decode_step", lane.bucket),
               "insert": ("insert", bb, lane.bucket)}.get(entry.kind)
        if key is None:
            raise ValueError(f"unknown warm-up entry kind {entry.kind!r}")
        fresh = key not in self._programs
        self._compiled(key, lane, cause="warmup")
        return fresh

    # -- observability --------------------------------------------------------
    @staticmethod
    def _leaf_bytes(leaves) -> int:
        total = 0
        for leaf in leaves:
            try:
                total += int(np.prod(leaf.shape)) \
                    * int(np.dtype(leaf.dtype).itemsize)
            except (TypeError, ValueError):
                continue
        return total

    def state_bytes_doc(self) -> Dict:
        """The ``kv_state`` ledger component, decomposed (PR 18):
        ``lanes`` (monolithic per-slot caches + a paged model's per-slot
        state — everything slot-shaped), ``paged_pool`` (the shared KV
        block pool), ``scales`` (int8 per-block scale planes) and ``aux``
        (per-slot host-side scheduler state: token cursors, block
        tables, position cursors — the PR 18 bugfix: these were never
        counted for unallocated lanes, so the gauge could under-report).
        Derived from leaf shapes/dtypes, so exact wherever jax placed
        the buffers."""
        import jax
        doc = {"lanes": 0, "paged_pool": 0, "scales": 0, "aux": 0}
        for lane in self._lanes:
            doc["aux"] += int(lane.tokens.nbytes)
            if isinstance(lane, _PagedLane):
                doc["aux"] += int(lane.tables.nbytes) + int(lane.pos.nbytes)
                # the model sorts its own state into the three classes
                for part, nb in self.inner.paged_state_bytes(
                        lane.state_shapes).items():
                    doc[part] += int(nb)
            elif lane.state is not None:
                doc["lanes"] += self._leaf_bytes(
                    jax.tree_util.tree_leaves(lane.state))
        # snapshot spool bytes (PR 20): host/disk-side, but pinned BY the
        # generation plane — the engine mirrors the spool size here so
        # the ledger's aux component owns continuity state too
        doc["aux"] += int(self.snapshot_bytes)
        doc["total"] = sum(doc.values())
        return doc

    def state_bytes(self) -> int:
        """Bytes pinned by decode state — the ``kv_state`` component of
        the resource ledger (PR 15): lane/pool device buffers plus the
        per-slot host-side scheduler state (see ``state_bytes_doc``)."""
        return int(self.state_bytes_doc()["total"])

    def program_stats(self) -> Dict:
        """Compiled scheduler programs + per-program execution counts
        (PR 15): the generation half of the per-program exec accounting,
        keyed like the ``aot.generation_manifest`` entries
        (``prefill:b<batch>xp<bucket>@<lane>`` etc.)."""
        progs = {k: v for k, v in self._programs.items()
                 if k and k[0] not in ("fns", "pfns")}
        return {"count": len(progs),
                "programs": dict(self._exec_counts)}

    def stats(self) -> Dict:
        d = {"slots_total": self.slots_total,
             "active_slots": self.active,
             "waiting": self.waiting,
             "decode_steps": self.decode_steps,
             "generated_tokens": self.generated_tokens,
             "admitted": self.admitted,
             "finished": self.finished,
             "quarantined": self.quarantined,
             "shed": self.shed,
             "compiles": self.compiles,
             "resumed": self.resumed,
             "resume_failed": self.resume_failed,
             "checkpoints": self.checkpoints,
             "snapshot_bytes": self.snapshot_bytes,
             "can_resume": bool(self._cache_model),
             "boundaries": self.boundaries,
             "intake_s_sum": self.intake_s_sum,
             "intake_n": self.intake_n,
             "queue_wait_s_sum": self.queue_wait_s_sum,
             "queue_wait_n": self.queue_wait_n,
             "prefill_s_sum": self.prefill_s_sum,
             "prefill_n": self.prefill_n,
             "first_out_s_sum": self.first_out_s_sum,
             "first_out_n": self.first_out_n,
             "prefill_positions_real": self.prefill_positions_real,
             "prefill_positions_padded": self.prefill_positions_padded,
             "state_bytes_passed": self.state_bytes_passed,
             "state_bytes_aliased": self.state_bytes_aliased,
             "pool_rebuilds": self.pool_rebuilds,
             "operand_builds": self.operand_builds,
             "operand_bytes": self.operand_bytes,
             "lanes": [{"bucket": lane.bucket,
                        "max_active": lane.max_active,
                        "active": lane.active}
                       for lane in self._lanes]}
        # where the generate thread's time went (PR 25): the phases
        # partition it, so phase_s.* sum to loop_s
        seconds, counts = self.clock.totals()
        for name in self.clock.phases:
            d["phase_s." + name] = seconds[name]
            d["phase_n." + name] = counts[name]
        d["loop_s"] = sum(seconds.values())
        # the start (PR 37): the marks set so far, and the sums over the
        # programs made so far (``program_records``)
        from analytics_zoo_tpu.inference import aot
        for name, t in self.startup.snapshot().items():
            d["startup_t." + name] = t
        d.update(aot.startup_totals(list(self.program_records)))
        for name, value in self.model_counters.items():
            d["model." + name] = value
        if self._pool is not None:
            pool = {"blocks": self._pool.n_blocks,
                    "block_len": self._pool.block_len,
                    "free_blocks": self._pool.free_blocks,
                    "used_blocks": self._pool.used_blocks,
                    "occupancy": round(
                        self._pool.used_blocks
                        / max(1, self._pool.n_blocks), 4),
                    "kv_quant": self.gen.kv_quant,
                    "exhausted": self.pool_exhausted}
            if self._prefix is not None:
                ps = self._prefix.stats()
                pool.update({"prefix_entries": ps["entries"],
                             "prefix_hits": ps["hits"],
                             "prefix_misses": ps["misses"],
                             "prefix_evictions": ps["evictions"]})
            d["pool"] = pool
        return d
